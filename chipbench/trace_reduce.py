"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy time and idle share, time by
operation name, the longest idle gaps with what the host was doing in
them, and roofline shares from bytes computed from shapes.

Events are plain ``(name, start_ns, end_ns)`` tuples on the trace's one
timeline; ``read`` pulls them out of the file, and every other function
works on such lists, so the arithmetic is tested without a chip.
"""
from __future__ import annotations

import glob
import os
from typing import Callable, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]

WINDOW_SPAN = "chipbench.window"


class Trace:
    """Events of one trace: ``device`` ops and ``modules`` per device
    plane, and every ``host`` event with a duration."""

    def __init__(self, device: List[List[Event]], modules: List[List[Event]],
                 host: List[Event]):
        self.device = device
        self.modules = modules
        self.host = host

    def window(self) -> Tuple[float, float]:
        """The traced window: the harness's ``chipbench.window`` span."""
        spans = [e for e in self.host if e[0] == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
        return spans[0][1], spans[0][2]


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def read(path: str, device_prefix: str = "/device:TPU:",
         op_line: str = "XLA Ops", module_line: str = "XLA Modules") -> Trace:
    """Events of the planes whose name starts with ``device_prefix``
    (lines ``op_line`` and ``module_line``) and of the host planes."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device, modules, host = [], [], []
    for plane in pd.planes:
        if plane.name.startswith(device_prefix):
            lines = {line.name: line for line in plane.lines}
            device.append(_events(lines.get(op_line)))
            modules.append(_events(lines.get(module_line)))
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(e for e in _events(line) if e[2] > e[1])
    return Trace(device, modules, host)


def _events(line) -> List[Event]:
    if line is None:
        return []
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    """Events cut to the window ``[lo, hi]``; those outside it dropped."""
    out = []
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((name, s, e))
    return out


def merged(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """The union of the events' intervals as sorted disjoint intervals."""
    out: List[List[float]] = []
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: Iterable[Event], lo: float, hi: float) -> float:
    """Nanoseconds of ``[lo, hi]`` in which some event runs."""
    return sum(e - s for s, e in merged(clip(events, lo, hi)))


def idle_gaps(events: Iterable[Event], lo: float,
              hi: float) -> List[Tuple[float, float]]:
    """The idle intervals of ``[lo, hi]``, longest first."""
    gaps, t = [], lo
    for s, e in merged(clip(events, lo, hi)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def short_name(name: str) -> str:
    """An XLA op event's instruction name (``%fusion.12``) without the
    HLO text after it."""
    return name.split(" = ", 1)[0] if name.startswith("%") else name


def self_times(events: Iterable[Event]) -> dict:
    """Nanoseconds per event name outside the events nested in it (a
    ``while`` op's event spans its whole loop)."""
    out: dict = {}
    stack: list = []                  # [name, end, self time]

    def close(until: float):
        while stack and stack[-1][1] <= until:
            name, _, t = stack.pop()
            out[name] = out.get(name, 0.0) + t

    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    close(float("inf"))
    return out


def matching(events: Iterable[Event], pred: Callable[[str], bool]):
    """``(count, nanoseconds)`` of the events whose name ``pred`` accepts."""
    n, t = 0, 0.0
    for name, s, e in events:
        if pred(name):
            n += 1
            t += e - s
    return n, t


def label(gap: Tuple[float, float], host: Sequence[Event]) -> str:
    """What the host was doing in ``gap``: the shortest host event that
    covers at least half of it, else the one that overlaps it most, else
    ``"host idle"``."""
    half = (gap[1] - gap[0]) / 2.0
    best, best_key = "host idle", None
    for name, s, e in host:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov <= 0:
            continue
        key = (0, e - s) if ov >= half else (1, -ov)
        if best_key is None or key < best_key:
            best, best_key = name, key
    return best


def roofline_share(bytes_moved: float, seconds: float,
                   bytes_per_s: float) -> Optional[float]:
    """Percent of the memory roofline: the least time the bytes need at
    peak bandwidth over the time taken. ``None`` with nothing timed."""
    if seconds <= 0 or bytes_moved <= 0:
        return None
    return 100.0 * bytes_moved / bytes_per_s / seconds


def breakdown(trace: Trace, lo: float, hi: float, k: int = 10) -> dict:
    """The contract's ``breakdown``: the device operations that took
    most time, and the longest idle gaps named by what the host did."""
    ops = [(short_name(n), s, e) for plane in trace.device
           for n, s, e in clip(plane, lo, hi)]
    top = sorted(self_times(ops).items(), key=lambda x: -x[1])[:k]
    gaps = idle_gaps(trace.device[0] if trace.device else [], lo, hi)[:k]
    host = [e for e in trace.host if e[0] != WINDOW_SPAN]
    return {
        "device_ops": [[name, ns / 1e9] for name, ns in top],
        "idle_gaps": [[label(g, host), (g[1] - g[0]) / 1e9] for g in gaps],
    }
