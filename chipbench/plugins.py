"""Parts of the harness found by name: ``chipbench/<kind>/<name>.py``.

Graph families, query kinds, vertex draws, arrival processes and
per-layer metric readers each live in a file of their own, named by the
configuration, the traffic mix or ``BENCHMARK.json``, so that a later
cell adds files and edits none.
"""
from __future__ import annotations

import importlib.util
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
_loaded: dict = {}


def path(kind: str, name: str, root: str = None) -> str:
    if not _NAME.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    base = HERE if root is None else os.path.join(root, "chipbench")
    return os.path.join(base, kind, name + ".py")


def load(kind: str, name: str, root: str = None):
    """The module ``chipbench/<kind>/<name>.py`` (under ``root`` when
    given), loaded once."""
    p = path(kind, name, root)
    if p not in _loaded:
        if not os.path.isfile(p):
            raise ValueError(f"no {kind} named {name!r} ({p} is missing)")
        mod_name = "chipbench_{}_{}".format(
            kind, re.sub(r"[^A-Za-z0-9_]", "_", name))
        spec = importlib.util.spec_from_file_location(mod_name, p)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[p] = mod
    return _loaded[p]
