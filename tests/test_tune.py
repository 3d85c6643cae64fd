"""The measured kernel autotuner (repro.tune): cache round-trips, candidate
seeding, resolve_spec mode semantics, and the padded-kernel prime-edge
regression — plus the bit-identity contract the tuner rests on (seed sets
and matrices never move, only wall time).
"""
import json
import types

import jax.numpy as jnp
import numpy as np
import pytest

from repro.graphs import rmat_graph
from repro.tune import (KernelConfig, TuningCache, cache_key, default_cache,
                        default_config, reset_default_cache,
                        schedule_candidates, size_bucket, spec_overrides,
                        sweep_candidates)
from repro.tune.autotuner import families_for, resolve_spec
from repro.tune.cache import CACHE_ENV, CACHE_VERSION


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------


def test_size_bucket_and_key():
    assert size_bucket(1) == 256
    assert size_bucket(256) == 256
    assert size_bucket(257) == 512
    assert size_bucket(5000) == 8192
    k = cache_key("sketch_propagate", backend="single", impl="ref",
                  model="wc", num_edges=5000)
    assert k == "sketch_propagate|single|ref|wc|e8192"
    # neighbors in the same bucket share an entry
    assert k == cache_key("sketch_propagate", backend="single", impl="ref",
                          model="wc", num_edges=4097)


def test_cache_round_trip(tmp_path):
    path = str(tmp_path / "tune.json")
    c = TuningCache(path)
    cfg = KernelConfig(edge_block=256, local_sweeps=1)
    c.put("k1", cfg, measurement={"speedup": 1.2})
    c.save()
    c2 = TuningCache(path)
    assert c2.lookup("k1") == cfg
    assert c2.record("k1")["measurement"]["speedup"] == 1.2
    assert len(c2) == 1
    assert c2.lookup("absent") is None and c2.record("absent") is None


def test_cache_corrupt_and_version_mismatch(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert len(TuningCache(str(bad))) == 0      # silently empty
    wrong = tmp_path / "wrong.json"
    wrong.write_text(json.dumps({"version": CACHE_VERSION + 1,
                                 "entries": {"k": {"config": {}}}}))
    assert TuningCache(str(wrong)).lookup("k") is None


def test_default_cache_env_override(tmp_path, monkeypatch):
    p = str(tmp_path / "env.json")
    monkeypatch.setenv(CACHE_ENV, p)
    reset_default_cache()
    assert default_cache().path == p
    monkeypatch.setenv(CACHE_ENV, "")           # disables persistence
    assert default_cache().path is None
    default_cache().save()                      # no-op, nothing written
    assert not tmp_path.joinpath("env.json").exists()
    monkeypatch.delenv(CACHE_ENV)
    reset_default_cache()


# ---------------------------------------------------------------------------
# candidate generation
# ---------------------------------------------------------------------------


def test_sweep_candidates_ref_clamped_and_unique():
    cands = sweep_candidates(100, impl="ref", default_chunk=2048)
    blocks = [c.edge_block for c in cands]
    assert len(blocks) == len(set(blocks))
    assert 100 in blocks and 2048 in blocks     # full-E + the default
    assert all(b <= 2048 for b in blocks)
    big = sweep_candidates(100_000, impl="ref")
    assert {128, 256, 2048, 8192, 100_000} <= {c.edge_block for c in big}


def test_sweep_candidates_pallas_grid():
    cands = sweep_candidates(10_000, impl="pallas")
    assert all(c.reg_tile in (128, 256) for c in cands)
    assert all(0 < c.edge_block <= 1024 for c in cands)


def test_schedule_candidates_seeded_from_measurement():
    # no stats at all: the conservative (0, 1) probe
    assert [c.local_sweeps for c in schedule_candidates(None, None)] == [0, 1]
    prof = types.SimpleNamespace(sweeps=1, step_bytes=np.array([700.0]))
    # comm is 30% of sweep traffic -> worth probing 1 and 2 local sweeps
    hot = types.SimpleNamespace(ring_bytes_per_sweep=300.0,
                                pad_waste_frac=0.5)
    assert [c.local_sweeps for c in schedule_candidates(hot, prof)] == [0, 1, 2]
    # comm is 2% -> extra sweeps are not worth timing
    cold = types.SimpleNamespace(ring_bytes_per_sweep=15.0,
                                 pad_waste_frac=0.5)
    assert [c.local_sweeps for c in schedule_candidates(cold, prof)] == [0]
    # global padding only offered when measured step-mode waste is small
    lean = types.SimpleNamespace(ring_bytes_per_sweep=300.0,
                                 pad_waste_frac=0.05)
    assert "global" in {c.pad_mode for c in schedule_candidates(lean, prof)}
    assert {c.pad_mode for c in schedule_candidates(hot, prof)} == {"step"}


def test_spec_overrides_mapping():
    from repro.runtime import RunSpec

    spec = RunSpec(num_registers=64)
    cfg = KernelConfig(edge_block=256, reg_tile=128, local_sweeps=1,
                       pad_mode="global")
    assert spec_overrides("sketch_propagate", cfg, spec) == {"edge_chunk": 256}
    assert spec_overrides("cascade_step", cfg, spec) == {"cascade_chunk": 256}
    assert spec_overrides("bucket_propagate", cfg, spec) == {
        "local_sweeps": 1, "pad_mode": "global"}
    fused = KernelConfig(fuse_sweeps=True, lane_fill=256)
    assert spec_overrides("fused_sweep", fused, spec) == {
        "fuse_sweeps": True, "lane_fill": 256}
    assert spec_overrides("fused_sweep", KernelConfig(), spec) == {
        "fuse_sweeps": False, "lane_fill": 0}
    assert spec_overrides("fused_sample", cfg, spec) == {}
    pal = spec.with_(impl="pallas")
    assert spec_overrides("sketch_propagate", cfg, pal) == {
        "edge_block": 256, "reg_tile": 128}
    # the all-defaults config resolves to the spec's own values
    assert spec_overrides("sketch_propagate", KernelConfig(), spec) == {
        "edge_chunk": spec.edge_chunk}


def test_families_for():
    from repro.runtime import RunSpec

    spec = RunSpec(num_registers=64)
    assert families_for(spec, "single") == ("sketch_propagate", "cascade_step")
    assert families_for(spec, "serial") == ()            # 1x1 grid: no ring
    sharded = spec.with_(mu_v=2, mu_s=2)
    assert families_for(sharded, "serial") == ("bucket_propagate",
                                               "fused_sweep")
    assert families_for(sharded, "mesh") == ("bucket_propagate",
                                             "fused_sweep")


def test_fused_candidates_seeded_from_measurement():
    from repro.tune import fused_candidates

    # no measurements: fills scale with the register count alone
    def fills(cands):
        return [c.lane_fill for c in cands]

    small = fused_candidates(None, None, model="wc", num_regs=128)
    assert fills(small) == [0] and all(c.fuse_sweeps for c in small)
    assert fills(fused_candidates(None, None, model="wc",
                                  num_regs=512)) == [0, 256]
    assert fills(fused_candidates(None, None, model="wc",
                                  num_regs=2048)) == [0, 256, 512]
    # lt's remixed hash spreads lanes -> a denser 128 slab is worth probing
    assert 128 in fills(fused_candidates(None, None, model="lt",
                                         num_regs=2048))
    assert 128 not in fills(fused_candidates(None, None, model="ic:0.2",
                                             num_regs=2048))
    # comm-dominated runs keep the slab probes; comm-free runs (<5% ring
    # traffic) collapse to the single full-width fused candidate
    prof = types.SimpleNamespace(sweeps=1, step_bytes=np.array([700.0]))
    cold = types.SimpleNamespace(ring_bytes_per_sweep=15.0,
                                 pad_waste_frac=0.5)
    assert fills(fused_candidates(cold, prof, model="wc",
                                  num_regs=2048)) == [0]
    hot = types.SimpleNamespace(ring_bytes_per_sweep=300.0,
                                pad_waste_frac=0.5)
    assert fills(fused_candidates(hot, prof, model="wc",
                                  num_regs=2048)) == [0, 256, 512]


# ---------------------------------------------------------------------------
# resolve_spec mode semantics
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small_graph():
    return rmat_graph(6, edge_factor=4, seed=7, setting="w1")


def test_resolve_spec_off_is_identity(small_graph):
    from repro.runtime import RunSpec

    spec = RunSpec(num_registers=64, seed=1)
    assert resolve_spec(small_graph, spec, backend="single") is spec
    # no graph -> no tuning, spec comes back untouched even in auto mode
    auto = spec.with_(tuning="auto")
    assert resolve_spec(None, auto, backend="single") is auto


def test_resolve_spec_rejects_unknown_mode(small_graph):
    from repro.runtime import RunSpec

    spec = RunSpec(num_registers=64, tuning="banana")
    with pytest.raises(ValueError):
        resolve_spec(small_graph, spec, backend="single")


def test_resolve_spec_cached_hit_and_miss(small_graph):
    from repro.runtime import RunSpec

    g = small_graph
    spec = RunSpec(num_registers=64, seed=1, tuning="cached")
    cache = TuningCache(None)                   # in-memory, cold
    out = resolve_spec(g, spec, backend="single", cache=cache)
    assert out.edge_chunk == spec.edge_chunk    # miss: deterministic fallback
    key = cache_key("sketch_propagate", backend="single", impl=spec.impl,
                    model=spec.model, num_edges=int(g.m))
    cache.put(key, KernelConfig(edge_block=256))
    out = resolve_spec(g, spec, backend="single", cache=cache)
    assert out.edge_chunk == 256                # hit: winner applied
    assert out.cascade_chunk == spec.cascade_chunk  # other family still miss
    assert out.tuning == "cached"               # mode itself never overridden


def test_resolve_spec_auto_measures_and_persists(small_graph):
    from repro.runtime import RunSpec

    g = small_graph
    spec = RunSpec(num_registers=64, seed=1, tuning="auto")
    cache = TuningCache(None)
    resolve_spec(g, spec, backend="single", cache=cache)
    assert len(cache) == 2                      # both single-device families
    for key, entry in cache.records().items():
        m = entry["measurement"]
        assert m["speedup"] >= 1.0              # default is always a candidate
        assert m["candidates"][0]["config"] == default_config(
            key.split("|")[0]).to_dict()
    # second resolve is pure cache hits (no new entries) applying the winner
    out = resolve_spec(g, spec, backend="single", cache=cache)
    assert len(cache) == 2
    winner = cache.lookup(cache_key("sketch_propagate", backend="single",
                                    impl=spec.impl, model=spec.model,
                                    num_edges=int(g.m)))
    assert out.edge_chunk == (winner.edge_block or spec.edge_chunk)


def test_tuning_bit_identity_single_backend(small_graph, tmp_path, monkeypatch):
    """The whole point: tuning="auto" measures and re-tiles, but seeds,
    spreads, and the canonical matrix are bit-identical to tuning="off"."""
    from repro.runtime import InfluenceSession, RunSpec

    monkeypatch.setenv(CACHE_ENV, str(tmp_path / "tune.json"))
    reset_default_cache()
    try:
        g = small_graph
        base = RunSpec(num_registers=64, seed=3, backend="single")
        res_off = InfluenceSession(g, base).find_seeds(4)
        m_off, _, _ = InfluenceSession(g, base).build_sketch_matrix()
        for mode in ("cached", "auto", "cached"):   # cached-cold, auto, warm
            sess = InfluenceSession(g, base.with_(tuning=mode))
            res = sess.find_seeds(4)
            np.testing.assert_array_equal(np.asarray(res.seeds),
                                          np.asarray(res_off.seeds))
            m_tuned, _, _ = sess.build_sketch_matrix()
            np.testing.assert_array_equal(np.asarray(m_tuned),
                                          np.asarray(m_off))
        assert tmp_path.joinpath("tune.json").exists()
    finally:
        monkeypatch.delenv(CACHE_ENV)
        reset_default_cache()


def test_tuning_bit_identity_serial_ring(small_graph, tmp_path, monkeypatch):
    """Ring-schedule tuning (local_sweeps / pad_mode) on the 2x2 serial
    backend: same seeds and matrix as the untuned ring."""
    from repro.runtime import InfluenceSession, RunSpec

    monkeypatch.setenv(CACHE_ENV, str(tmp_path / "tune.json"))
    reset_default_cache()
    try:
        g = small_graph
        base = RunSpec(num_registers=64, seed=3, backend="serial",
                       mu_v=2, mu_s=2)
        res_off = InfluenceSession(g, base).find_seeds(4)
        sess = InfluenceSession(g, base.with_(tuning="auto"))
        res = sess.find_seeds(4)
        np.testing.assert_array_equal(np.asarray(res.seeds),
                                      np.asarray(res_off.seeds))
        cache = default_cache()
        key = cache_key("bucket_propagate", backend="serial", impl=base.impl,
                        model=base.model, num_edges=int(g.m))
        assert cache.lookup(key) is not None
    finally:
        monkeypatch.delenv(CACHE_ENV)
        reset_default_cache()


# ---------------------------------------------------------------------------
# serial local_sweeps result-invariance (the knob the ring tuner moves)
# ---------------------------------------------------------------------------


def test_serial_local_sweeps_result_invariant(small_graph):
    from repro.core.difuser import DiFuserConfig
    from repro.core.sampling import make_x_vector
    from repro.partition.serial import build_matrix_ring_serial

    g = small_graph.sorted_by_dst()
    cfg = DiFuserConfig(num_registers=64, seed=5)
    x = np.sort(np.asarray(make_x_vector(64, seed=5), dtype=np.uint32))
    mats = [build_matrix_ring_serial(g, cfg, x, mu_v=2, mu_s=2,
                                     local_sweeps=ls)[0]
            for ls in (0, 1, 2)]
    np.testing.assert_array_equal(mats[0], mats[1])
    np.testing.assert_array_equal(mats[0], mats[2])


# ---------------------------------------------------------------------------
# prime-edge-count regression (kernels/common.py pad+mask instead of a
# largest-divisor block search, whose worst case was block=1 on prime axes)
# ---------------------------------------------------------------------------


def test_clamp_and_pad_prime_axis():
    from repro.kernels.common import clamp_block, pad_amount

    assert clamp_block(997, 512) == 512         # clamp ...
    assert pad_amount(997, 512) == 27           # ... and pad to 1024
    assert clamp_block(251, 512) == 251         # block never exceeds the axis
    assert pad_amount(251, 251) == 0


@pytest.mark.parametrize("m_prime", [251, 509])
def test_pallas_sweeps_prime_edge_count(m_prime):
    """Pallas edge kernels on a prime edge count (no divisor-friendly
    block exists) must still match the jnp oracle bit-exactly — the padded
    tail is predicate-dead (thr=0 never fires)."""
    from repro.core.sampling import make_x_vector, weight_to_threshold
    from repro.kernels import ops

    g = rmat_graph(7, edge_factor=8, seed=2, setting="u01").sorted_by_dst()
    assert g.m >= m_prime
    src = jnp.asarray(g.src[:m_prime])
    dst = jnp.asarray(g.dst[:m_prime])
    thr = jnp.asarray(weight_to_threshold(g.weight[:m_prime]))
    x = jnp.asarray(make_x_vector(128, seed=3))
    m0 = ops.sketch_fill(jnp.zeros((g.n_pad, 128), jnp.int8), impl="ref")
    ref = ops.propagate_sweep(m0, src, dst, thr, x, impl="ref")
    pal = ops.propagate_sweep(m0, src, dst, thr, x, impl="pallas",
                              edge_block=64)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(pal))
    mc = m0.at[0].set(-1)
    ref_c = ops.cascade_sweep(mc, src, dst, thr, x, impl="ref")
    pal_c = ops.cascade_sweep(mc, src, dst, thr, x, impl="pallas",
                              edge_block=64)
    np.testing.assert_array_equal(np.asarray(ref_c), np.asarray(pal_c))


def test_ref_sweep_chunk_invariant(small_graph):
    """The scan-chunk knob the tuner moves on the ref impl is bit-invariant
    (including chunk = full E: no scan at all)."""
    from repro.core.sampling import make_x_vector, weight_to_threshold
    from repro.kernels import ops

    g = small_graph.sorted_by_dst()
    src, dst = jnp.asarray(g.src), jnp.asarray(g.dst)
    thr = jnp.asarray(weight_to_threshold(g.weight))
    x = jnp.asarray(make_x_vector(64, seed=1))
    m0 = ops.sketch_fill(jnp.zeros((g.n_pad, 64), jnp.int8), impl="ref")
    outs = [np.asarray(ops.propagate_sweep(m0, src, dst, thr, x, impl="ref",
                                           edge_chunk=c))
            for c in (7, 128, 2048, int(src.shape[0]))]
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)


def test_fused_sweep_matches_sweep_loop(small_graph):
    """The fused multi-sweep kernel is bit-identical to S separate
    propagate_sweep launches, for every lane_fill (including a non-divisor
    slab width) on the ref impl and every lane tile on the Pallas impl."""
    from repro.core.sampling import make_x_vector, weight_to_threshold
    from repro.kernels import ops

    g = small_graph.sorted_by_dst()
    src, dst = jnp.asarray(g.src), jnp.asarray(g.dst)
    thr = jnp.asarray(weight_to_threshold(g.weight))
    x = jnp.asarray(make_x_vector(64, seed=1))
    m0 = ops.sketch_fill(jnp.zeros((g.n_pad, 64), jnp.int8), impl="ref")
    oracle = m0
    for _ in range(3):
        oracle = ops.propagate_sweep(oracle, src, dst, thr, x, impl="ref")
    oracle = np.asarray(oracle)
    for lf in (0, 16, 24):                      # 24 does not divide 64
        out = ops.fused_sweep(m0, src, dst, thr, x, num_sweeps=3,
                              impl="ref", lane_fill=lf)
        np.testing.assert_array_equal(oracle, np.asarray(out))
    for tile in (16, 64):
        out = ops.fused_sweep(m0, src, dst, thr, x, num_sweeps=3,
                              impl="pallas", lane_fill=tile)
        np.testing.assert_array_equal(oracle, np.asarray(out))


@pytest.mark.parametrize("m_prime", [251, 509])
def test_fused_sweep_prime_edge_count(m_prime):
    """Fused Pallas sweeps on a prime edge count: the pad+mask path (padded
    tail is predicate-dead) must hold across every fused iteration, not just
    the first — a sticky bit leaking from the pad would compound per sweep."""
    from repro.core.sampling import make_x_vector, weight_to_threshold
    from repro.kernels import ops

    g = rmat_graph(7, edge_factor=8, seed=2, setting="u01").sorted_by_dst()
    assert g.m >= m_prime
    src = jnp.asarray(g.src[:m_prime])
    dst = jnp.asarray(g.dst[:m_prime])
    thr = jnp.asarray(weight_to_threshold(g.weight[:m_prime]))
    x = jnp.asarray(make_x_vector(128, seed=3))
    m0 = ops.sketch_fill(jnp.zeros((g.n_pad, 128), jnp.int8), impl="ref")
    oracle = m0
    for _ in range(2):
        oracle = ops.propagate_sweep(oracle, src, dst, thr, x, impl="ref")
    pal = ops.fused_sweep(m0, src, dst, thr, x, num_sweeps=2, impl="pallas",
                          lane_fill=64)
    np.testing.assert_array_equal(np.asarray(oracle), np.asarray(pal))
    ref = ops.fused_sweep(m0, src, dst, thr, x, num_sweeps=2, impl="ref",
                          lane_fill=48)
    np.testing.assert_array_equal(np.asarray(oracle), np.asarray(ref))
