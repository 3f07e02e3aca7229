"""Fixtures for the benchmark's own tests: a scratch checkout whose
traffic is cut to a size the CPU runs in a second."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (REPO, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

# per traffic file: the rungs at rehearsal size (same divisibility as the
# real ones: multiples of the 8192-lane chunk, a grid edge of k*1 + 2)
TINY = {"hbm_ladder": [8192, 16384, 24576],
        "small_ladder": [8192, 16384, 32768],
        "hbm_grid": [66],
        "top_message": [4096]}


# cells whose files are in perfbench/ but that BENCHMARK.json may not
# list yet: a later change adds them with these entries and no other edit
PENDING = {
    "configs": [
        {"name": "allreduce_2x2", "source": "nccl-tests",
         "file": "perfbench/configs/allreduce_2x2.json", "reduced": ["b"],
         "why": "all-reduce"}],
    "workloads": [
        {"name": "triad.small", "config": "stream_triad",
         "traffic": "small_ladder", "chips": 1, "why": "dispatch"},
        {"name": "allreduce.4chip", "config": "allreduce_2x2",
         "traffic": "top_message", "chips": 4, "why": "all-reduce"}],
    "end_to_end": [
        {"name": "ici_GBps", "unit": "GB/s", "better": "higher",
         "bound": 0.05, "source": "host_clock",
         "workloads": ["allreduce.4chip"]}],
    "per_layer": [
        {"name": "idle_share.ici", "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "device", "moves": "ici_GBps",
         "workloads": ["allreduce.4chip"]}],
}
# the cells each metric of the memory cells is reported in
MEMORY_CELLS = ("triad.small",)


def with_pending(bench: dict) -> dict:
    """``bench`` with every pending cell, configuration and metric it
    lacks; metrics that list cells gain the pending cells they cover."""
    have = {g: {e["name"] for e in bench[g]} for g in PENDING}
    for group, entries in PENDING.items():
        bench[group] += [e for e in entries if e["name"] not in have[group]]
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells = m.get("workloads")
        if cells is None:
            continue
        if "triad.hbm" in cells:
            cells += [w for w in MEMORY_CELLS if w not in cells]
        if m["name"] in ("stage_s", "pass_ms_p95", "setup_s") and \
                "allreduce.4chip" not in cells:
            cells.append("allreduce.4chip")
    return bench


def make_root(dest: pathlib.Path) -> pathlib.Path:
    """A checkout at ``dest``: the benchmark's files, ``BENCHMARK.json``
    with the pending cells, and the program (linked), with every traffic
    cut to ``TINY``."""
    shutil.copytree(REPO / "perfbench", dest / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (dest / "BENCHMARK.json").write_text(json.dumps(with_pending(bench)))
    (dest / "src").symlink_to(REPO / "src")
    for name, rungs in TINY.items():
        path = dest / "perfbench" / "traffic" / f"{name}.json"
        traffic = json.loads(path.read_text())
        traffic.update(n=rungs, reps=2)
        path.write_text(json.dumps(traffic))
    return dest


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """A scratch checkout; jax's persistent compile cache stays off in
    this process, so no later test in the worker inherits it."""
    import repro.core.staging as staging

    monkeypatch.setattr(staging, "enable_persistent_cache", lambda d: d)
    return make_root(tmp_path)
