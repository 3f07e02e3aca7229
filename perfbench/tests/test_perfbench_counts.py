"""The benchmark's yardstick at sizes one can count by hand: the bytes each
configuration's call must move, the peaks table, and which metrics a
cell reports."""
from __future__ import annotations

import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from perfbench import harness  # noqa: E402


def _config(name):
    cfg = harness.load_json(REPO / "perfbench" / "configs" / f"{name}.json")
    ref = harness.load_module(REPO / "perfbench" / "configs" / f"{name}.py")
    return cfg, ref


def test_triad_counts_twelve_bytes_per_point():
    cfg, ref = _config("stream_triad")
    # 4 rows of 8 f32: b and c read (2 x 32 x 4 B), a written (32 x 4 B)
    assert ref.traffic_bytes(cfg, 8) == {"hbm": 384}
    assert ref.traffic_bytes(cfg, 1 << 26)["hbm"] == 12 * (1 << 28)


def test_jacobi2d_counts_grid_read_and_interior_written():
    cfg, ref = _config("jacobi2d_5pt")
    # two 4 x 4 grids: 16 points read, the 2 x 2 interior written, 4 B each
    assert ref.traffic_bytes(cfg, 4) == {"hbm": 2 * 80}
    assert ref.traffic_bytes(cfg, 16386)["hbm"] == \
        2 * 4 * (16386 ** 2 + 16384 ** 2)


def test_jacobi2d_grid_reaches_hbm():
    """Every grid of the committed traffic is at least 4x the largest
    on-chip store (v5e's 128 MiB of VMEM), and the grid pairs one call
    holds fit the chip's HBM."""
    cfg, _ = _config("jacobi2d_5pt")
    traffic = harness.load_json(REPO / "perfbench" / "traffic"
                                / "hbm_grid.json")
    programs = int(cfg["driver_config"]["programs"])
    hbm = harness.load_peaks(REPO, "TPU v5 lite")["hbm_bytes"]
    for n in traffic["n"]:
        grid = 4 * n * n
        assert grid >= 4 * 128 * 2**20, n
        assert 2 * programs * grid <= hbm, n


def test_allreduce_counts_ring_wire_bytes():
    cfg, ref = _config("allreduce_2x2")
    # k = 4, 8 f32 per device: each device sends 2 (k-1)/k of 32 B = 48 B
    assert ref.traffic_bytes(cfg, 8) == {"ici": 4 * 48}
    assert ref.traffic_bytes(cfg, 1 << 25)["ici"] == 805306368


def test_peaks_table_holds_v5e_with_its_source():
    row = harness.load_peaks(REPO, "TPU v5 lite")
    assert (row["hbm_GBps"], row["hbm_bytes"], row["ici_Gbps"],
            row["bf16_TFLOPs"]) == (819, 16_000_000_000, 1600, 197)
    table = harness.load_json(REPO / "perfbench" / "peaks.json")
    assert "TPU v5e" in table["source"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="not in peaks.json"):
        harness.load_peaks(REPO, "cpu")


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    bench = harness.load_json(REPO / "BENCHMARK.json")
    e2e = {m["name"] for m in bench["end_to_end"]}
    for cell in bench["workloads"]:
        got = {m["name"] for m in harness.metrics_for(
            bench, cell["name"], "end_to_end")}
        assert "setup_s" in got and len(got) >= 2, cell["name"]
        layers = harness.metrics_for(bench, cell["name"], "per_layer")
        assert layers and {m["moves"] for m in layers} <= e2e


def test_every_named_file_exists():
    bench = harness.load_json(REPO / "BENCHMARK.json")
    pb = REPO / "perfbench"
    for c in bench["configs"]:
        cfg = harness.load_json(REPO / c["file"])
        assert cfg["name"] == c["name"]
        assert (REPO / c["file"]).with_suffix(".py").is_file()
        assert (pb / "entries" / f"{cfg['entry']}.py").is_file()
    for w in bench["workloads"]:
        assert (pb / "traffic" / f"{w['traffic']}.json").is_file()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (pb / "metrics" / f"{m['name']}.py").is_file()


def test_seed_key_keeps_the_high_bits():
    import jax

    def bits(seed):
        return jax.random.key_data(harness.seed_key(seed)).tolist()

    assert bits(7) != bits(7 + (1 << 32))
    assert bits(2**31 + 5) == bits(2**31 + 5)
