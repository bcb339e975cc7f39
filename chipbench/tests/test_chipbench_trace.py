"""The trace reduction, on hand-made events and on a small trace
recorded on the CPU (``data/cpu_trace.xplane.pb``)."""
import os

import pytest

from chipbench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_union_idle_and_gaps_by_hand():
    ev = [("a", 10, 20), ("b", 15, 30), ("c", 40, 45), ("d", 0, 5)]
    # window [2, 50]: busy [2,5] + [10,30] + [40,45] = 3 + 20 + 5
    assert tr.busy_ns(ev, 2, 50) == 28
    assert tr.idle_gaps(ev, 2, 50) == [(30, 40), (5, 10), (45, 50)]
    assert tr.clip(ev, 2, 50) == [("a", 10, 20), ("b", 15, 30),
                                  ("c", 40, 45), ("d", 2, 5)]
    assert tr.matching(ev, lambda s: s in "ab") == (2, 25)


def test_self_time_leaves_out_nested_events():
    # a while op spanning [0, 100] with a body op [10, 30] and a nested
    # pair [40, 80] > [50, 60]; a sibling op after it
    ev = [("%while.1 = (s32[]) while(...)", 0, 100), ("%f.2 = add", 10, 30),
          ("outer", 40, 80), ("inner", 50, 60), ("after", 100, 110)]
    named = [(tr.short_name(n), s, e) for n, s, e in ev]
    assert named[0][0] == "%while.1"
    assert tr.self_times(named) == {"%while.1": 40, "%f.2": 20,
                                    "outer": 30, "inner": 10, "after": 10}


def test_gap_label_is_the_host_event_covering_it_most():
    host = [("outer", 0, 100), ("submit", 31, 39), ("wait", 44, 60),
            ("brief", 52, 53)]
    assert tr.label((30, 40), host) == "submit"
    assert tr.label((45, 50), host) == "wait"
    assert tr.label((50, 60), host) == "wait"
    assert tr.label((38, 70), [("x", 30, 45), ("y", 60, 80)]) == "y"
    assert tr.label((200, 300), host) == "host idle"


def test_roofline_share():
    # 819e6 bytes in 2 ms at 819 GB/s: 1 ms needed, 50 %
    assert tr.roofline_share(819e6, 2e-3, 819e9) == pytest.approx(50.0)
    assert tr.roofline_share(1.0, 0.0, 819e9) is None


def test_recorded_cpu_trace():
    t = tr.read(os.path.join(DATA, "cpu_trace.xplane.pb"),
                device_prefix="/host:CPU", op_line="python")
    lo, hi = t.window()
    assert (lo, hi) == (133281.0, 12342477.0)
    ops = [e for e in t.device[0] if e[0].startswith("op.")]
    assert ops == [("op.a", 2239284.0, 5309132.0),
                   ("op.b", 6414701.0, 8606481.0),
                   ("op.c", 6981177.0, 8043788.0)]
    # op.c lies inside op.b: busy = |a| + |b|
    busy = (5309132 - 2239284) + (8606481 - 6414701)
    assert tr.busy_ns(ops, lo, hi) == busy
    assert tr.idle_gaps(ops, lo, hi)[0] == (8606481.0, 12342477.0)
    idle_share = 1 - busy / (hi - lo)
    assert idle_share == pytest.approx(0.56904, abs=1e-5)
    host = [e for e in t.host if e[0] != tr.WINDOW_SPAN]
    # the python tracer's "$time sleep" (2.0 ms) and host.wait (1.6 ms)
    # lie in the 3.7 ms gap; only the first covers half of it
    assert tr.label((8606481.0, 12342477.0), host) == "$time sleep"
