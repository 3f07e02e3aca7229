"""Reduce a profiler trace of the measured window to device numbers.

``load`` reads the newest ``.xplane.pb`` under a directory with
``jax.profiler.ProfileData`` and keeps, per device:

- ``ops``: the intervals in which an operation ran (a TPU's ``XLA Ops``
  line; on the CPU backend, the host events that carry an ``hlo_op``);
- ``runs``: one interval per execution of a program (a TPU's ``XLA
  Modules`` line; on the CPU backend, the ops of one ``run_id``);

and the benchmark's own host spans (names that start with a prefix).
Everything else here works on those intervals, in nanoseconds.
"""
from __future__ import annotations

import bisect
import dataclasses
import pathlib
from collections import defaultdict

OPS_LINE = "XLA Ops"
RUNS_LINE = "XLA Modules"
TOP = 10


@dataclasses.dataclass
class Trace:
    ops: dict        # device -> [(start_ns, end_ns, name), ...] sorted
    runs: dict       # device -> [(start_ns, end_ns, name), ...] sorted
    host: list       # [(start_ns, end_ns, name), ...] sorted


def load(trace_dir, span_prefix: str = "") -> Trace:
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(str(files[-1])), span_prefix)


def from_profile(pd, span_prefix: str = "") -> Trace:
    ops, runs, host = defaultdict(list), defaultdict(list), []
    host_planes = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                into = {OPS_LINE: ops, RUNS_LINE: runs}.get(line.name)
                if into is not None:
                    into[plane.name].extend(_intervals(line.events))
        elif plane.name.startswith("/host:"):
            host_planes.append(plane)
    cpu_runs = defaultdict(lambda: [None, None, ""])
    host_ops = not ops  # no device plane: the CPU backend's ops
    for plane in host_planes:
        for line in plane.lines:
            for ev in line.events:
                if span_prefix and ev.name.startswith(span_prefix):
                    host.append(_interval(ev, ev.name[len(span_prefix):]))
                elif host_ops:
                    _cpu_op(ev, ops, cpu_runs)
    for (dev, _), (lo, hi, name) in cpu_runs.items():
        runs[dev].append((lo, hi, name))
    return Trace(ops={d: sorted(v) for d, v in ops.items()},
                 runs={d: sorted(v) for d, v in runs.items()},
                 host=sorted(host))


def _interval(ev, name=None):
    start = int(ev.start_ns)
    return (start, start + int(ev.duration_ns),
            short_name(ev.name) if name is None else name)


def short_name(name: str) -> str:
    """A TPU op event carries its HLO instruction's text
    (``%fusion.3 = f32[...] fusion(...)``): keep the instruction's name."""
    head, sep, _ = name.partition(" = ")
    return head.lstrip("%") if sep else name


def _intervals(events):
    return [_interval(ev) for ev in events]


def _cpu_op(ev, ops, cpu_runs) -> None:
    """The CPU backend runs ops on host threads: an event with an
    ``hlo_op`` stat is an op of device ``device_ordinal``, and the ops of
    one ``run_id`` make one execution of ``hlo_module``."""
    stats = dict(ev.stats)
    if "hlo_op" not in stats:
        return
    dev = f"/cpu:{stats.get('device_ordinal', 0)}"
    lo, hi, _ = iv = _interval(ev)
    ops[dev].append(iv)
    run = cpu_runs[(dev, stats.get("run_id"))]
    run[0] = lo if run[0] is None else min(run[0], lo)
    run[1] = hi if run[1] is None else max(run[1], hi)
    run[2] = str(stats.get("hlo_module", ""))


def union(intervals) -> list[tuple[int, int]]:
    """Merge ``(start, end, ...)`` intervals into disjoint ones, sorted."""
    out: list[list[int]] = []
    for lo, hi, *_ in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def busy_s(tr: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    if not tr.ops:
        return 0.0
    per = [sum(hi - lo for lo, hi in union(v)) for v in tr.ops.values()]
    return sum(per) / len(per) / 1e9


def run_s(tr: Trace) -> float:
    """Seconds of program executions, averaged over the devices."""
    if not tr.runs:
        return 0.0
    per = [sum(hi - lo for lo, hi, _ in v) for v in tr.runs.values()]
    return sum(per) / len(per) / 1e9


def launch_gaps_s(tr: Trace) -> list[float]:
    """Idle seconds between consecutive executions, pooled over devices."""
    gaps = []
    for v in tr.runs.values():
        merged = union(v)
        gaps += [(b[0] - a[1]) / 1e9 for a, b in zip(merged, merged[1:])]
    return gaps


def idle_gaps(tr: Trace) -> dict:
    """Idle device time between operations, summed per host activity:
    each gap goes to the innermost benchmark span that covers its middle,
    averaged over devices."""
    layers = _layers(tr.host)
    by_name: dict = defaultdict(float)
    for v in tr.ops.values():
        merged = union(v)
        for a, b in zip(merged, merged[1:]):
            mid = (a[1] + b[0]) // 2
            by_name[_covering(layers, mid)] += (b[0] - a[1]) / 1e9
    n = max(1, len(tr.ops))
    return {k: s / n for k, s in by_name.items()}


def _layers(spans) -> list[list]:
    """Nested spans split into layers of disjoint spans, outermost first:
    each span joins the first layer whose last span has ended."""
    layers: list[list] = []
    for s in sorted(spans, key=lambda s: (s[0], -s[1])):
        for layer in layers:
            if layer[-1][1] <= s[0]:
                layer.append(s)
                break
        else:
            layers.append([s])
    return layers


def _covering(layers, t) -> str:
    """The name of the innermost span that covers ``t``."""
    for layer in reversed(layers):
        i = bisect.bisect_right(layer, (t, float("inf"), "")) - 1
        if i >= 0 and layer[i][0] <= t <= layer[i][1]:
            return layer[i][2]
    return "outside the benchmark's spans"


def self_times(ops) -> dict:
    """Seconds per op name, each op less the ops nested inside it (a
    loop's own time is what its body's ops leave uncovered)."""
    per_op: dict = defaultdict(float)
    stack: list = []   # [end, name, own_ns] of the enclosing ops

    def close(entry):
        per_op[entry[1]] += entry[2] / 1e9

    for lo, hi, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= lo:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(hi, stack[-1][0]) - lo
        stack.append([hi, name, hi - lo])
    while stack:
        close(stack.pop())
    return per_op


def breakdown(tr: Trace) -> dict:
    """The device operations that took most time (self time), and the idle
    time by what the host was doing, each the top ``TOP``, averaged over
    devices."""
    per_op: dict = defaultdict(float)
    for v in tr.ops.values():
        for name, sec in self_times(v).items():
            per_op[name] += sec
    n = max(1, len(tr.ops))
    top_ops = sorted(((k, s / n) for k, s in per_op.items()),
                     key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(idle_gaps(tr).items(), key=lambda kv: -kv[1])[:TOP]
    return {"device_ops": [[k, s] for k, s in top_ops],
            "idle_gaps": [[k, s] for k, s in top_gaps]}
