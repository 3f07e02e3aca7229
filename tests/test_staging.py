"""Translation-cache + staged-pipeline tests.

Covers the PR-1 acceptance contract: hit/miss accounting across repeated
``Driver.run`` working sets, invalidation when env / schedule / template
change, cached-vs-cold output equivalence, compile-time reporting, the
vectorized oracle fast path, and once-per-variant validation.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import (
    Driver, DriverConfig, TranslationCache, Variant, identity, jacobi1d,
    jacobi2d, jacobi3d, serial_oracle, stage_lower, sweep, triad,
)
from repro.core import drivers as drivers_mod

WS = [256, 512, 1024]  # three working sets, per the acceptance criteria


def _cfg(**kw):
    base = dict(template="unified", programs=4, ntimes=2, reps=1,
                validate_n=64)
    base.update(kw)
    return DriverConfig(**base)


# ---------------------------------------------------------------------------
# hit/miss accounting
# ---------------------------------------------------------------------------


def test_repeated_runs_hit_cache_across_working_sets():
    cache = TranslationCache()
    d = Driver(lambda env: triad(), _cfg(), cache=cache)

    d.run(WS)
    s1 = cache.stats()
    assert s1["lower_misses"] == len(WS)
    assert s1["compile_misses"] == len(WS)
    assert s1["lower_hits"] == 0 and s1["compile_hits"] == 0

    d.run(WS)  # identical tuples: nothing may lower or compile again
    s2 = cache.stats()
    assert s2["lower_misses"] == len(WS)
    assert s2["compile_misses"] == len(WS)
    assert s2["lower_hits"] >= len(WS)
    assert s2["compile_hits"] >= len(WS)
    assert s2["hit_rate"] > 0


def test_fresh_driver_same_structure_still_hits():
    """Factories rebuild PatternSpec objects per call; the structural
    fingerprint must identify them anyway."""
    cache = TranslationCache()
    Driver(lambda env: triad(), _cfg(), cache=cache).run([512])
    Driver(lambda env: triad(), _cfg(), cache=cache).run([512])
    s = cache.stats()
    assert s["lower_misses"] == 1 and s["lower_hits"] == 1
    assert s["compile_misses"] == 1 and s["compile_hits"] == 1


def test_cache_keys_invalidate_on_config_changes():
    cache = TranslationCache()
    Driver(lambda env: triad(), _cfg(), cache=cache).run([512])
    base = cache.stats()["lower_misses"]

    # different env (working set)
    Driver(lambda env: triad(), _cfg(), cache=cache).run([513 - 1 + 256])
    assert cache.stats()["lower_misses"] == base + 1

    # different schedule
    Driver(lambda env: triad(),
           _cfg(schedule=identity().interleave("i", 2)),
           cache=cache).run([512])
    assert cache.stats()["lower_misses"] == base + 2

    # different template
    Driver(lambda env: triad(), _cfg(template="independent"),
           cache=cache).run([512])
    assert cache.stats()["lower_misses"] == base + 3

    # different pattern constants (combine closure) must not collide
    Driver(lambda env: triad(scalar=2.0), _cfg(), cache=cache).run([512])
    assert cache.stats()["lower_misses"] == base + 4


def test_ntimes_change_recompiles_but_shares_lowering():
    cache = TranslationCache()
    d1 = Driver(lambda env: triad(), _cfg(ntimes=2), cache=cache)
    d2 = Driver(lambda env: triad(), _cfg(ntimes=4), cache=cache)
    d1.run([512])
    d2.run([512])
    s = cache.stats()
    assert s["lower_misses"] == 1 and s["lower_hits"] >= 1
    assert s["compile_misses"] == 2


# ---------------------------------------------------------------------------
# cached-vs-cold equivalence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("template", ["unified", "independent"])
def test_cached_output_equals_cold_output(template):
    cold_cache, warm_cache = TranslationCache(), TranslationCache()
    mk = lambda c: Driver(lambda env: triad(), _cfg(template=template),
                          cache=c)
    _, _, env, compiled_cold, tup, names = mk(cold_cache).build({"n": 512})

    warm = mk(warm_cache)
    warm.build({"n": 512})                       # prime
    _, _, _, compiled_warm, tup2, _ = warm.build({"n": 512})
    assert compiled_warm.from_cache

    out_cold = compiled_cold(tup)
    out_warm = compiled_warm(tup2)
    for a, b in zip(out_cold, out_warm):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_record_fields_and_compile_time_reporting():
    cache = TranslationCache()
    d = Driver(lambda env: triad(), _cfg(), cache=cache)
    (rec,) = d.run([512])
    assert rec.gbs > 0 and rec.seconds > 0
    assert rec.extra["barrier"] is False
    assert rec.extra["compile_seconds"] >= 0
    assert rec.extra["lower_seconds"] >= 0
    assert rec.extra["cache_hit"] is False
    (rec2,) = d.run([512])
    assert rec2.extra["cache_hit"] is True
    # cached replay preserves the record identity fields
    for f in ("pattern", "template", "schedule", "backend", "n",
              "working_set_bytes", "programs", "ntimes", "level"):
        assert getattr(rec2, f) == getattr(rec, f)


# ---------------------------------------------------------------------------
# validation memo + sweep sharing
# ---------------------------------------------------------------------------


def test_validate_runs_once_per_variant(monkeypatch):
    calls = []
    real = drivers_mod.serial_oracle

    def spy(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(drivers_mod, "serial_oracle", spy)
    cache = TranslationCache()
    d = Driver(lambda env: triad(), _cfg(), cache=cache)
    d.validate()
    d.validate()
    Driver(lambda env: triad(), _cfg(), cache=cache).validate()
    assert len(calls) == 1


def test_sweep_shares_cache_and_reports_stats():
    cache = TranslationCache()
    variants = [
        Variant("a", _cfg(template="independent", programs=2)),
        Variant("b", _cfg(template="independent", programs=2,
                          schedule=identity().interleave("i", 2))),
    ]
    res = sweep(lambda env: triad(), variants, [256, 512], cache=cache)
    assert res.best[0] in ("a", "b")
    assert res.cache_stats is not None
    assert res.cache_stats["lower_misses"] >= 4
    # sweeping again is pure cache hits for lowering + compilation
    res2 = sweep(lambda env: triad(), variants, [256, 512], cache=cache)
    assert res2.cache_stats["lower_misses"] == res.cache_stats["lower_misses"]
    assert res2.cache_stats["compile_misses"] == res.cache_stats["compile_misses"]
    assert res2.cache_stats["compile_hits"] > res.cache_stats["compile_hits"]


# ---------------------------------------------------------------------------
# staged artifacts directly
# ---------------------------------------------------------------------------


def test_stage_lower_pallas_keyed_separately():
    cache = TranslationCache()
    pat = triad()
    env = {"n": 256}
    stage_lower(pat, identity(), env, "jax", cache=cache)
    stage_lower(pat, identity(), env, "pallas", cache=cache)
    stage_lower(pat, identity(), env, "jax", cache=cache)
    s = cache.stats()
    assert s["lower_misses"] == 2 and s["lower_hits"] == 1


# ---------------------------------------------------------------------------
# vectorized oracle fast path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("factory,sch,env", [
    # vectorized fast path (single band per dim, write space never read)
    (triad, identity(), {"n": 64}),
    (triad, identity().interleave("i", 2), {"n": 64}),
    (triad, identity().reverse("i"), {"n": 64}),
    (jacobi1d, identity(), {"n": 66}),
    (jacobi2d, identity().interchange("i", "j"), {"n": 18}),
    (jacobi3d, identity(), {"n": 10}),
    # unified-template shape: programs split via outer tile, inner intact
    (triad, identity().tile("i", 16, outer="prog", inner="i"), {"n": 64}),
    # tiled nests fall back to the point loop; equality must still hold
    (jacobi1d, identity().tile("i", 16), {"n": 66}),
])
def test_vectorized_oracle_matches_point_loop(factory, sch, env):
    pat = factory()
    nest = sch.lower(pat.domain, env)
    arrays = pat.allocate(env)
    fast = serial_oracle(pat, nest, arrays, env, ntimes=2)
    slow = serial_oracle(pat, nest, arrays, env, ntimes=2, force_loop=True)
    for k in slow:
        np.testing.assert_allclose(fast[k], slow[k], rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# donated specialized measurement executables (PR-5)
# ---------------------------------------------------------------------------


def test_prepare_donates_specialized_executables():
    """Measurement executables from ``prepare`` donate their buffers: a
    call consumes its input tuple (no working-set-sized copy survives to
    be observed), ``bind`` threads outputs into subsequent calls, and a
    foreign tuple mid-stream raises instead of being silently ignored."""
    import jax.numpy as jnp

    d = Driver(lambda env: triad(), _cfg(parametric=False),
               cache=TranslationCache())
    (p,) = d.prepare([512])
    assert p.compiled.donated and not p.parametric
    arrays = p.lowered.pattern.allocate(p.lowered.env)
    tup = tuple(jnp.asarray(arrays[k]) for k in p.compiled.names)
    fn = p.executable()
    out1 = fn(tup)
    out2 = fn(tup)          # timing loop re-passes the seed: threads out1
    assert all(o.shape == t.shape for o, t in zip(out2, out1))
    # the seed tuple's buffers were donated away on the first call
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(tup[0])
    # a brand-new tuple cannot join an existing donated stream
    fresh = tuple(jnp.asarray(v) for v in
                  (np.zeros(512, np.float32),) * len(tup))
    with pytest.raises(ValueError, match="threads its buffers"):
        fn(fresh)
    # and calling the raw executable with consumed buffers fails loudly
    with pytest.raises(Exception):
        p.compiled.run(tup)


def test_build_stays_undonated_and_recallable():
    """``Driver.build`` keeps the re-callable undonated compile (library
    callers replay tuples), and the donate flag is part of the cache
    key, so the two executables never collide."""
    cache = TranslationCache()
    d = Driver(lambda env: triad(), _cfg(parametric=False), cache=cache)
    _, _, _, compiled, tup, _ = d.build({"n": 512})
    assert compiled.donated is False
    a = compiled(tup)
    b = compiled(tup)       # same tuple twice: undonated must allow it
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    d.prepare([512])        # donated twin compiles separately
    assert cache.stats()["compile_misses"] == 2


def test_donated_records_match_undonated_records():
    """Donation must not change what is measured: records from the
    donated measurement path carry the same identity fields and values
    as a run through the undonated executable."""
    import jax.numpy as jnp

    cache = TranslationCache()
    d = Driver(lambda env: triad(), _cfg(parametric=False), cache=cache)
    (rec,) = d.run([1024])
    assert rec.extra["param_path"] == "specialized"
    assert rec.extra["donated"] is True
    # undonated twin executed by hand on the same arrays
    lw = d.lower({"n": 1024})
    c = lw.compile(ntimes=d.cfg.ntimes, donate=False, cache=cache)
    arrays = lw.pattern.allocate(lw.env)
    tup = tuple(jnp.asarray(arrays[k]) for k in c.names)
    out = c(tup)
    donated = d.prepare([1024])[0]
    arrays2 = donated.lowered.pattern.allocate(donated.lowered.env)
    tup2 = tuple(jnp.asarray(arrays2[k]) for k in donated.compiled.names)
    out2 = donated.executable()(tup2)
    for x, y in zip(out, out2):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# concurrent cache access (PR-8 — the ThreadPoolBackend contract)
# ---------------------------------------------------------------------------


def test_concurrent_same_key_staging_builds_once():
    """Eight threads race the same lowering key on a fresh cache: the
    builder must run exactly once (the others block on the cache lock
    and hit), and the counters must account for every request."""
    import threading

    cache = TranslationCache()
    pat = triad()
    sch = identity()
    barrier = threading.Barrier(8)
    errors = []

    def worker():
        try:
            barrier.wait()
            stage_lower(pat, sch, {"n": 512}, cache=cache)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    s = cache.stats()
    assert s["lower_misses"] == 1
    assert s["lower_hits"] == 7


def test_wrapped_device_index_shares_cache_entry():
    """Device-axis indices that resolve (modulo the visible device
    count) to the same physical device must share one cache entry —
    a collapsed plan (dev0..devN on a smaller box) should not compile
    duplicate identical executables."""
    import jax

    cache = TranslationCache()
    pat = triad()
    sch = identity()
    ndev = len(jax.devices())
    a = stage_lower(pat, sch, {"n": 512}, device=0, cache=cache)
    b = stage_lower(pat, sch, {"n": 512}, device=ndev, cache=cache)
    assert a is b
    s = cache.stats()
    assert s["lower_misses"] == 1
    assert s["lower_hits"] == 1
    # an unpinned lowering stays a distinct entry (ambient default
    # device is not necessarily devices()[0] under default_device scopes)
    stage_lower(pat, sch, {"n": 512}, device=None, cache=cache)
    assert cache.stats()["lower_misses"] == 2


def test_concurrent_mixed_keys_eviction_counters_consistent():
    """Concurrent distinct-key traffic through a capacity-2 LRU: no
    torn counter updates — hits + misses equals the request count and
    evictions never exceeds insertions minus capacity."""
    import threading

    cache = TranslationCache(capacity=2)
    pat = triad()
    sch = identity()
    sizes = [256, 512, 1024, 2048]
    rounds = 4
    barrier = threading.Barrier(len(sizes))
    errors = []

    def worker(n):
        try:
            barrier.wait()
            for _ in range(rounds):
                stage_lower(pat, sch, {"n": n}, cache=cache)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(n,)) for n in sizes]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    s = cache.stats()
    requests = len(sizes) * rounds
    assert s["lower_hits"] + s["lower_misses"] == requests
    assert s["lower_misses"] >= len(sizes)  # every key missed at least once
    assert s["evictions"] >= s["lower_misses"] - 2  # capacity-2 LRU
    assert 0.0 <= s["hit_rate"] <= 1.0


def test_disk_counter_listener_updates_are_locked():
    """The jax disk-cache monitoring listener increments shared counters
    from compile threads; hammer it from many threads and demand no
    lost updates."""
    import threading

    from repro.core import staging as staging_mod

    before = staging_mod.disk_cache_stats()
    with staging_mod._disk_lock:
        pass  # the lock object exists and is a real lock

    def worker():
        for _ in range(1000):
            with staging_mod._disk_lock:
                staging_mod._disk_counters["hits"] += 1

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    after = staging_mod.disk_cache_stats()
    assert after["hits"] - before["hits"] == 8000
    with staging_mod._disk_lock:
        staging_mod._disk_counters["hits"] = before["hits"]


_DONATED_FROM_DISK = """
import json
import jax.numpy as jnp
import numpy as np
from repro.core import Driver, DriverConfig, triad
from repro.core.staging import disk_cache_stats, enable_persistent_cache

enable_persistent_cache("unused-when-the-variable-is-set")
d = Driver(lambda env: triad(), DriverConfig(
    template="independent", programs=2, parametric="auto", ntimes=2,
    reps=1))
ladder = [1024, 4096]
d.run(ladder)
p = d.prepare(ladder, parallel=False)[-1]
arrays = p.lowered.pattern.allocate(p.lowered.env)
out = p.executable()(tuple(jnp.asarray(arrays[k]) for k in p.compiled.names))
print(json.dumps({"disk": disk_cache_stats(), "donated": p.parametric,
                  "A": float(np.asarray(out[0]).min()),
                  "A_max": float(np.asarray(out[0]).max())}))
"""


def test_donated_executables_load_back_from_the_disk_cache(tmp_path):
    """A second cold process finds the donated parametric executable in
    jax's persistent cache (placed by JAX_COMPILATION_CACHE_DIR) and
    runs it to the right answer: no process-unique module names."""
    import json
    import os
    import pathlib
    import subprocess
    import sys

    root = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(root / "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    runs = []
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", _DONATED_FROM_DISK],
                             env=env, capture_output=True, text=True,
                             timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    first, second = runs
    assert first["disk"]["enabled"] and first["disk"]["hits"] == 0
    assert second["disk"]["hits"] > 0 and second["disk"]["misses"] == 0
    for r in runs:
        assert r["donated"] and r["A"] == r["A_max"] == 3.0 + 3.0 * 4.0
    assert any((tmp_path / "cache").iterdir())
