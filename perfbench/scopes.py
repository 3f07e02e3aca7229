"""Device time by program scope.

Each device op of a traced window is put under the ``repro.*`` scope
that its HLO instruction carries in the executable's text, as the
program maps it (``repro.core.spans.device_scopes``): a TPU's op events
carry no ``op_name``. Ops that map to no scope count as ``unscoped``;
ops outside any program execution are not counted.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

from . import trace

UNSCOPED = "unscoped"


def seconds(tr) -> dict | None:
    """``{scope: device self time in seconds}`` over the ops inside the
    window's program executions, averaged over the devices; None where
    the trace is empty or the program keeps no scope map."""
    if tr is None or not tr.ops:
        return None
    try:
        from repro.core import spans
    except ImportError:
        return None
    maps = getattr(spans, "device_scopes", lambda: {})()
    if not maps:
        return None
    merged: dict = {}
    for m in maps.values():
        merged.update(m)
    per: dict = defaultdict(float)
    for dev, ops in tr.ops.items():
        runs = tr.runs.get(dev, [])
        starts = [r[0] for r in runs]
        by_run: dict = defaultdict(list)
        for op in ops:
            i = bisect.bisect_right(starts, op[0]) - 1
            if i >= 0 and op[0] < runs[i][1]:
                by_run[i].append(op)
        for i, run_ops in by_run.items():
            table = maps.get(_module(runs[i][2]), merged)
            for name, sec in trace.self_times(run_ops).items():
                per[table.get(name, UNSCOPED)] += sec
    n = max(1, len(tr.ops))
    return {k: v / n for k, v in sorted(per.items())}


def _module(name: str) -> str:
    """A module's name as its executable's text gives it: an execution
    event may add the program's id in parentheses."""
    return re.sub(r"\(.*\)$", "", name).strip()
