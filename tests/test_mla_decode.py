"""The MLA decode step as a registry pattern (``suite/decode.py``), on the
CPU at a small size: the Driver's path against the benchmark's plain
reference and the model's decompressed form, its byte count, the
bf16 tile accounting of its cache and the device scopes of its parts."""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ArchConfig, MLAConfig
from repro.core import Driver, DriverConfig, TranslationCache, spans
from repro.core.measure import tile_traffic
from repro.suite.decode import mla_decode

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))
PB = REPO / "perfbench"
# narrow widths (cut here only): 4 heads of 16 + 8 over a latent of 32
TINY = ArchConfig(
    name="mla-tiny", family="moe", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=4, d_ff=64, vocab_size=32, head_dim=16,
    mla=MLAConfig(q_lora_rank=0, kv_lora_rank=32, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16))
L, B, T = 2, 2, 64


def _load(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load(PB / "configs" / "deepseek_v2_lite_mla.py", "mla_ref")


def _ref_cfg(arch: ArchConfig, layers: int, batch: int) -> dict:
    m = arch.mla
    return {"num_attention_heads": arch.n_heads, "hidden_size": arch.d_model,
            "qk_nope_head_dim": m.qk_nope_head_dim,
            "qk_rope_head_dim": m.qk_rope_head_dim,
            "v_head_dim": m.v_head_dim, "kv_lora_rank": m.kv_lora_rank,
            "rms_norm_eps": 1e-6, "rope_theta": arch.rope_theta,
            "pattern_args": {"layers": layers, "batch": batch}}


def _staged(pattern, n=T, sync_every_rep=False):
    d = Driver(lambda env: pattern,
               DriverConfig(template="unified", programs=1, ntimes=1,
                            sync_every_rep=sync_every_rep),
               cache=TranslationCache())
    (p,) = d.prepare([n], parallel=False)
    arrays = pattern.allocate(p.lowered.env)
    return p, arrays


@pytest.mark.parametrize("sync_every_rep", [False, True])
def test_driver_path_matches_plain_reference(ref, sync_every_rep):
    from perfbench.compare import compare, exact

    pat = mla_decode(TINY, L, B)
    p, arrays = _staged(pat, sync_every_rep=sync_every_rep)
    assert not p.parametric and p.compiled.donated
    tup = tuple(jnp.asarray(arrays[k]) for k in p.compiled.names)
    fn = p.executable()
    fn(tup)
    out = dict(zip(p.compiled.names, fn(tup)))   # idempotent: a second call
    want = ref.reference({k: jnp.asarray(v) for k, v in arrays.items()}, T,
                         _ref_cfg(TINY, L, B), exact)
    rel, mism = compare({k: out[k] for k in want}, want)
    assert int(mism) == 0
    assert 0 < float(rel) < 0.03
    # the read-only operands come back as they went in, undonated
    assert all(out[k] is t for k, t in zip(p.compiled.names, tup)
               if k not in pat.writes)


def test_driver_validate_against_numpy_oracle():
    d = Driver(lambda env: mla_decode(TINY, L, B),
               DriverConfig(template="unified", programs=1, ntimes=2,
                            validate_n=T), cache=TranslationCache())
    d.validate()


def test_absorbed_step_matches_decompressed_mla_apply():
    from repro.models.attention import mla_apply

    pat = mla_decode(TINY, L, B)
    p, arrays = _staged(pat)
    out = dict(zip(p.compiled.names, p.executable()(
        tuple(jnp.asarray(arrays[k]) for k in p.compiled.names))))
    a = {k: jnp.asarray(v) for k, v in arrays.items()}   # the call donated
    pos = int(arrays["pos"][0])
    weights = [k for k in a if k.startswith("w_") or k == "kv_norm"]
    for layer in range(L):
        cache = {"ckv": a["ckv"][layer], "krope": a["krope"][layer],
                 "len": pos}
        h, new = mla_apply({k: a[k][layer] for k in weights}, a["x"],
                           n_heads=TINY.n_heads, mla=TINY.mla,
                           positions=jnp.full((B, 1), pos, jnp.int32),
                           rope_theta=TINY.rope_theta, cache=cache,
                           absorbed_decode=False)
        np.testing.assert_array_equal(np.asarray(out["ckv"][layer]),
                                      np.asarray(new["ckv"]))
        np.testing.assert_array_equal(np.asarray(out["krope"][layer]),
                                      np.asarray(new["krope"]))
        got = np.asarray(out["h"][layer], np.float32)
        dec = np.asarray(h, np.float32)
        rms = np.sqrt(np.mean(dec * dec, -1, keepdims=True))
        assert np.max(np.abs(got - dec) / rms) < 0.03


def test_pattern_bytes_equal_the_benchmark_count(ref):
    cfg = json.loads((PB / "configs" / "deepseek_v2_lite_mla.json")
                     .read_text())
    args = cfg["pattern_args"]
    n = 32768
    pat = mla_decode(args["arch"], args["layers"], args["batch"])
    count = ref.traffic_bytes(cfg, n)
    assert pat.sweep_bytes({"n": n}) == count["hbm"] == 6_535_450_628
    assert count["cache_read"] == 24 * 32768 * 7 * 1152
    # the configuration's published widths are the ArchConfig's
    m = pat.space("ckv").concrete_shape({"n": n})
    assert m == (7, 24, n, cfg["kv_lora_rank"])
    assert pat.space("krope").concrete_shape({"n": n})[-1] == \
        cfg["qk_rope_head_dim"]
    assert pat.space("w_q").concrete_shape({"n": n}) == (
        7, cfg["hidden_size"], cfg["num_attention_heads"]
        * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]))


def test_tile_traffic_counts_bf16_tiles_by_each_space_type():
    rows = 1000
    bf16 = tile_traffic(spaces={"k": (rows, 64)},
                        program_slices=[{"k": (0, rows * 64)}],
                        written="k", itemsizes={"k": 2})
    f32 = tile_traffic(spaces={"k": (rows, 64)},
                       program_slices=[{"k": (0, rows * 64)}], written="k")
    # a bf16 tile holds 16 x 128 elements, an f32 one 8 x 128
    assert bf16.fetches == -(-rows * 64 // 2048)
    assert f32.fetches == -(-rows * 64 // 1024)
    # the Driver counts each space by its own type: the bf16 cache and
    # the int32 position alike
    pat = mla_decode(TINY, L, B)
    d = Driver(lambda env: pat, DriverConfig(template="unified", programs=1),
               cache=TranslationCache())
    env = {"n": 2048}    # one bf16 tile of each space, two f32 ones
    got = d._traffic(pat, env)
    slices = [{s.name: (0, 2048) for s in pat.spaces}]
    spaces = {s.name: s.concrete_shape(env) for s in pat.spaces}
    assert got == tile_traffic(
        spaces=spaces, program_slices=slices, written="h",
        itemsizes={s.name: 4 if s.name == "pos" else 2 for s in pat.spaces})
    assert got != tile_traffic(spaces=spaces, program_slices=slices,
                               written="h")


def test_scope_map_finds_the_three_mla_scopes():
    pat = mla_decode(TINY, 1, B)
    p, _ = _staged(pat)
    module, scopes = spans.scope_map(p.compiled.executable.as_text())
    assert module.startswith("jit_")
    assert {"repro.mla.project", "repro.mla.append",
            "repro.mla.scan"} <= set(scopes.values())
    assert spans.device_scopes()[module].items() >= scopes.items()
