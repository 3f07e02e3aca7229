"""mla.decode32k rehearsed through ``harness.run_cell`` on the CPU: the
published widths, with the context, the layers held and the batch cut
in the scratch checkout's own copies of the traffic and configuration."""
from __future__ import annotations

import json
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from perfbench import harness  # noqa: E402

CELL = "mla.decode32k"
PEAKS = {"hbm_GBps": 819}  # the CPU has no row in peaks.json


def _cut(root, n, layers, batch):
    pb = root / "perfbench"
    path = pb / "traffic" / "decode_32k.json"
    path.write_text(json.dumps({**json.loads(path.read_text()),
                                "n": [n], "reps": 2}))
    path = pb / "configs" / "deepseek_v2_lite_mla.json"
    cfg = json.loads(path.read_text())
    cfg["pattern_args"].update(layers=layers, batch=batch)
    path.write_text(json.dumps(cfg))
    return root


@pytest.fixture
def mla_root(tiny_root):
    """The scratch checkout with mla.decode32k cut to two layers of two
    sequences at a context of 64 rows."""
    return _cut(tiny_root, 64, 2, 2)


def _build(root, seed):
    bench = harness.load_json(root / "BENCHMARK.json")
    _, cfg_entry = harness.find_cell(bench, CELL)
    cfg = harness.load_json(root / cfg_entry["file"])
    ref = harness.load_module((root / cfg_entry["file"]).with_suffix(".py"))
    traffic = harness.load_json(root / "perfbench" / "traffic"
                                / "decode_32k.json")
    entry = harness.load_module(root / "perfbench" / "entries"
                                / "typed_driver.py")
    return entry.build(cfg, ref, traffic, seed)


def _run(root, trace, seed=2**33 + 17):
    return harness.run_cell(CELL, seed, 0.3, trace, root=root,
                            require_tpu=False, peaks=PEAKS)


def test_mla_cell_runs_correct_with_its_metrics(mla_root):
    bench = harness.load_json(mla_root / "BENCHMARK.json")
    r = _run(mla_root, False)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {
        m["name"] for m in harness.metrics_for(bench, CELL, "end_to_end")}
    assert r["check"]["exact_mismatches"]["value"] == 0
    assert 0 < r["check"]["max_rel_err"]["value"] \
        < r["check"]["max_rel_err"]["limit"]

    t = _run(mla_root, True)
    assert t["correct"]
    assert set(t["metrics"]) == {
        m["name"] for m in harness.metrics_for(bench, CELL, "per_layer")}
    assert 0 < t["metrics"]["scan_roofline.mla"]["value"]
    assert 0 <= t["metrics"]["append_share.mla"]["value"] < 100


def test_mla_check_fails_an_answer_off_by_one_row(mla_root):
    """The check sees a wrong row: the appended row landing one position
    early breaks the bit-for-bit rows."""
    cell = _build(mla_root, 5)
    call = cell.calls[0]
    out = call.run()
    names = sorted(["ckv", "h", "krope", "kv_norm", "pos", "w_dkv", "w_o",
                    "w_q", "w_uk", "w_uv", "x"])
    i = names.index("ckv")
    bad = list(out)
    bad[i] = out[i].at[:, :, -2].set(out[i][:, :, -1])
    call.keep(tuple(bad))
    checks, failed = cell.check()
    got = {name: value for name, value, _ in checks}
    assert failed == 1 and got["exact_mismatches"] > 0


def test_mla_limit_lies_between_program_and_control(tiny_root):
    """At the cell's own context (one layer of one sequence), the
    program reads under ``max_rel_err``'s limit and the reference
    computed in bfloat16, in its place, over it: bf16 accumulation
    across the 256 blocks of 128 rows shows."""
    cell = _build(_cut(tiny_root, 32768, 1, 1), 2**33 + 3)
    call = cell.calls[0]
    call.keep(call.run())
    program, failed = cell.check()
    got = {name: (value, limit) for name, value, limit in program}
    assert failed == 0 and got["exact_mismatches"][0] == 0
    assert 0 < got["max_rel_err"][0] < got["max_rel_err"][1]
    control, failed = cell.check(use_control=True)
    got = {name: (value, limit) for name, value, limit in control}
    assert failed == 1 and got["exact_mismatches"][0] == 0
    assert got["max_rel_err"][0] > got["max_rel_err"][1]


def test_draw_holds_the_stored_value_when_widened_in_one_computation():
    """The check draws a bf16 space again and widens it inside its own
    computation. The draw rounds by ``reduce_precision``, which XLA keeps
    where it may drop a float32 -> bfloat16 -> float32 round trip, so
    the widened value is the one a bf16 buffer stores."""
    import jax
    import jax.numpy as jnp

    entry = harness.load_module(REPO / "perfbench" / "entries"
                                / "typed_driver.py")
    key = jax.random.key(7)
    stored = entry.draw(key, (4096,), jnp.bfloat16, 0.5)
    assert stored.dtype == jnp.bfloat16
    widened = jax.jit(lambda k: entry.draw(k, (4096,), jnp.bfloat16, 0.5)
                      .astype(jnp.float32))
    assert "reduce_precision" in str(jax.make_jaxpr(widened)(key))
    assert bool((widened(key) == stored.astype(jnp.float32)).all())
    same = entry.draw(key, (4096,), jnp.float32, 0.5)
    assert same.dtype == jnp.float32
    assert "reduce_precision" not in str(jax.make_jaxpr(
        lambda k: entry.draw(k, (4096,), jnp.float32, 0.5))(key))
