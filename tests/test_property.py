"""Hypothesis property tests on the system's invariants.

``hypothesis`` is an optional test dependency (not baked into the runtime
image); the module skips cleanly when it is absent so plain ``pytest -x -q``
still collects the rest of the suite.
"""
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from repro.core.sampling import clz32, edge_hash, mix32, weight_to_threshold
from repro.core.sketch import VISITED, merge

regs = st.lists(st.integers(min_value=-1, max_value=32), min_size=4, max_size=4)


def _m(vals):
    return jnp.asarray(np.array(vals, dtype=np.int8)[None, :])


@settings(max_examples=60, deadline=None)
@given(regs, regs)
def test_merge_commutative_modulo_visited(a, b):
    """merge(a,b) == merge(b,a) wherever neither side is VISITED; VISITED
    positions are sticky to the *left* operand (the paper's in-place
    update)."""
    ab = np.asarray(merge(_m(a), _m(b)))[0]
    ba = np.asarray(merge(_m(b), _m(a)))[0]
    for i, (x, y) in enumerate(zip(a, b)):
        if x != VISITED and y != VISITED:
            assert ab[i] == ba[i] == max(x, y)


@settings(max_examples=60, deadline=None)
@given(regs, regs, regs)
def test_merge_contribution_associative(a, b, c):
    """The law the kernels rely on: the destination guard commutes with
    accumulating contributions by plain max —
        merge(merge(a, b), c) == merge(a, max(b, c)).
    (Plain associativity of ``merge`` itself does NOT hold: VISITED is
    sticky only on the destination side, by design.)"""
    import jax.numpy as jnp

    lhs = merge(merge(_m(a), _m(b)), _m(c))
    rhs = merge(_m(a), jnp.maximum(_m(b), _m(c)))
    np.testing.assert_array_equal(np.asarray(lhs), np.asarray(rhs))


@settings(max_examples=60, deadline=None)
@given(regs)
def test_merge_idempotent(a):
    m = _m(a)
    np.testing.assert_array_equal(np.asarray(merge(m, m)), np.asarray(m))


@settings(max_examples=60, deadline=None)
@given(regs, regs)
def test_merge_monotone_and_visited_sticky(a, b):
    out = np.asarray(merge(_m(a), _m(b)))[0]
    for i, x in enumerate(a):
        if x == VISITED:
            assert out[i] == VISITED  # visited never resurrects
        else:
            assert out[i] >= x  # monotone non-decreasing


@settings(max_examples=40, deadline=None)
@given(st.integers(min_value=0, max_value=2**32 - 1))
def test_clz32_definition(v):
    x = np.array([v], dtype=np.uint32)
    expect = 32 if v == 0 else 32 - int(v).bit_length()
    assert clz32(x)[0] == expect


@settings(max_examples=30, deadline=None)
@given(st.integers(min_value=0, max_value=2**31 - 1),
       st.integers(min_value=0, max_value=2**31 - 1))
def test_edge_hash_deterministic(u, v):
    a = edge_hash(np.array([u]), np.array([v]))
    b = edge_hash(np.array([u]), np.array([v]))
    assert a[0] == b[0]


@settings(max_examples=40, deadline=None)
@given(st.floats(min_value=0.0, max_value=1.0, allow_nan=False))
def test_threshold_in_range(w):
    thr = weight_to_threshold(np.array([w], np.float32))
    assert 0 <= int(thr[0]) <= 0xFFFFFFFF


@settings(max_examples=60, deadline=None)
@given(st.integers(min_value=0, max_value=2**32 - 1),
       st.integers(min_value=0, max_value=2**32 - 1),
       st.integers(min_value=0, max_value=2**32 - 1))
def test_fused_predicate_lo_zero_is_legacy_compare(h, thr, x):
    """The model zoo's universal interval predicate with lo = 0 is
    bit-identical to the paper's threshold compare (X ^ h) < thr — the wc
    backward-compatibility contract at the predicate level."""
    from repro.core.sampling import fused_predicate

    hv = np.array([h], dtype=np.uint32)
    tv = np.array([thr], dtype=np.uint32)
    xv = np.array([x], dtype=np.uint32)
    lo = np.zeros(1, dtype=np.uint32)
    legacy = (hv ^ xv) < tv
    np.testing.assert_array_equal(fused_predicate(hv, lo, tv, xv), legacy)


@settings(max_examples=30, deadline=None)
@given(st.lists(st.tuples(st.integers(min_value=0, max_value=15),
                          st.integers(min_value=0, max_value=15),
                          st.floats(min_value=0.0, max_value=1.0,
                                    allow_nan=False, width=32)),
                min_size=1, max_size=40))
def test_wc_registry_bit_identical_to_legacy_path(edges):
    """``wc`` through the diffusion registry lowers to exactly the legacy
    per-edge operands: h = edge_hash(src, dst, seed), lo = 0,
    thr = weight_to_threshold(weight) — so every wc sample decision (and
    hence every wc seed set) is byte-identical to the pre-zoo path."""
    from repro.core.sampling import fused_predicate, make_x_vector, sample_mask
    from repro.diffusion import resolve
    from repro.graphs.structs import Graph

    src, dst, w = (np.array(c) for c in zip(*edges))
    g = Graph.from_edges(16, src, dst, w.astype(np.float32), edge_block=8)
    ep = resolve("wc").edge_params(g, seed=3)
    legacy_h = edge_hash(g.src, g.dst, seed=3)
    legacy_thr = weight_to_threshold(g.weight)
    np.testing.assert_array_equal(ep.h, legacy_h)
    np.testing.assert_array_equal(ep.thr, legacy_thr)
    assert not ep.lo.any()
    x = make_x_vector(16, seed=1)
    np.testing.assert_array_equal(
        fused_predicate(ep.h[:, None], ep.lo[:, None], ep.thr[:, None], x[None, :]),
        sample_mask(legacy_h, legacy_thr, x))


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=2**32 - 1),
                min_size=8, max_size=64, unique=True))
def test_partition_preserves_sample_multiset(xs):
    """FASST is a permutation of the sample space: the multiset of sampled
    graphs is invariant (paper §4.1)."""
    from repro.core.fasst import partition_samples

    x = np.array(xs[: len(xs) // 4 * 4], dtype=np.uint32)
    if x.size == 0:
        return
    shards, perm = partition_samples(x, 4, method="fasst")
    assert sorted(shards.reshape(-1).tolist()) == sorted(x.tolist())
    np.testing.assert_array_equal(x[perm], shards.reshape(-1))


# ---------------------------------------------------------------------------
# Repair equivalence (ISSUE 5): serial shard repair == full rebuild for every
# (diffusion model, partition strategy); plus mesh repair == both where the
# host has >= 4 devices — the CI run with 8 fake devices, where this
# property is the bitwise acceptance gate.
# ---------------------------------------------------------------------------

_REPAIR_MODELS = ["wc", "ic:0.2", "dic:0.5"]   # lt rebuilds by design
_REPAIR_STRATEGIES = ["block", "degree", "edge", "random"]
_REPAIR_MU_V = 4


def _mesh_repair_ready():
    import jax

    return len(jax.devices()) >= _REPAIR_MU_V


@settings(max_examples=12, deadline=None)
@given(st.integers(min_value=0, max_value=len(_REPAIR_MODELS) - 1),
       st.integers(min_value=0, max_value=len(_REPAIR_STRATEGIES) - 1),
       st.lists(st.tuples(st.integers(min_value=0, max_value=95),
                          st.integers(min_value=0, max_value=95)),
                min_size=1, max_size=8),
       st.integers(min_value=0, max_value=5))
def test_repair_plan_shards_equals_rebuild_all_backends(mi, si, adds, seed):
    """Property: for a random insertion delta, frontier-restricted shard
    repair (serial — and mesh, when it can run here) produces a matrix
    bitwise equal to a pristine full rebuild, across every context-free
    diffusion model and every partition strategy. ``lt`` is excluded: its
    interval renormalization makes insertion repair unsound, so apply_delta
    rebuilds instead (covered by tests/test_diffusion.py)."""
    from repro.core.difuser import DiFuserConfig
    from repro.graphs import rmat_graph
    from repro.graphs.structs import GraphDelta
    from repro.partition import plan_partition
    from repro.service import SketchStore, apply_delta

    model = _REPAIR_MODELS[mi]
    strategy = _REPAIR_STRATEGIES[si]
    g = rmat_graph(6, edge_factor=5, seed=seed, setting="w1")
    cfg = DiFuserConfig(num_registers=64, seed=seed, model=model)
    src = np.array([a % g.n for a, _ in adds], dtype=np.int64)
    dst = np.array([b % g.n for _, b in adds], dtype=np.int64)
    keep = src != dst
    if not keep.any():
        return
    delta = GraphDelta.make(add=(src[keep], dst[keep]), default_weight=0.6)

    def repaired_matrix(backend):
        store = SketchStore()
        e = store.get_or_build(g, cfg)
        store.attach_plan(e.key, plan_partition(
            e.graph, _REPAIR_MU_V, mu_s=1, strategy=strategy, x=e.x,
            seed=seed, model=model))
        if backend == "mesh":
            from repro.launch.mesh import make_serving_mesh

            e.place_on_mesh(make_serving_mesh(_REPAIR_MU_V))
        rep = apply_delta(store, e.key, delta, backend=backend)
        assert rep.repair_backend == backend or rep.added == 0
        return np.asarray(store.entry(e.key).matrix)

    serial_m = repaired_matrix("serial")

    store = SketchStore()
    e = store.get_or_build(g, cfg)
    apply_delta(store, e.key, delta)        # historical per-bank repair
    np.testing.assert_array_equal(serial_m, np.asarray(store.entry(e.key).matrix))
    store.rebuild(e.key)                    # pristine rebuild, same graph
    np.testing.assert_array_equal(serial_m, np.asarray(store.entry(e.key).matrix))

    if _mesh_repair_ready():
        np.testing.assert_array_equal(repaired_matrix("mesh"), serial_m)


# ---------------------------------------------------------------------------
# Kernel-config bit-identity (ISSUE 8 + 10): the knobs the autotuner moves
# — scan chunks, cascade chunks, ring local_sweeps, bucket pad_mode, and
# the fused-sweep pair (fuse_sweeps, lane_fill) — are
# performance-only. Seed sets, gains, and the canonical sketch matrix are
# byte-identical across every sampled KernelConfig x diffusion model x
# backend. The mesh twin executes where the host has enough devices (the
# CI run with 8 fake devices); its ring consumes the same (local_sweeps,
# pad_mode) knobs through DistributedConfig.
# ---------------------------------------------------------------------------

_TUNE_MODELS = ["wc", "ic:0.2", "lt", "dic:0.5"]
#: RunSpec overrides the tuner could emit (spec_overrides output space);
#: {} is today's defaults — the baseline every other point must match
_TUNE_OVERRIDES = [
    {},
    {"edge_chunk": 7, "cascade_chunk": 7},
    {"edge_chunk": 128, "cascade_chunk": 512},
    {"edge_chunk": 1 << 20},                   # >= m: one unscanned sweep
    {"local_sweeps": 1},
    {"local_sweeps": 2, "pad_mode": "global"},
    # fused_sweep family (ISSUE 10): the local_sweeps prologue through the
    # fused multi-sweep kernel, at full width and at lane fills that slab
    # the 32-register axis evenly (8) and raggedly (24, a non-divisor)
    {"local_sweeps": 2, "fuse_sweeps": True},
    {"local_sweeps": 2, "fuse_sweeps": True, "lane_fill": 8},
    {"local_sweeps": 1, "fuse_sweeps": True, "lane_fill": 24,
     "pad_mode": "global"},
]

_tune_baselines: dict = {}


def _tune_run(model, backend, overrides):
    from repro.graphs import rmat_graph
    from repro.runtime import InfluenceSession, RunSpec

    g = _tune_baselines.setdefault(
        "graph", rmat_graph(6, edge_factor=4, seed=11, setting="w1"))
    spec = RunSpec(num_registers=32, seed=11, model=model, backend=backend,
                   mu_v=2 if backend != "single" else 1,
                   mu_s=2 if backend != "single" else 1,
                   **overrides)
    sess = InfluenceSession(g, spec)
    res = sess.find_seeds(3)
    m, _, _ = sess.build_sketch_matrix()
    return np.asarray(res.seeds), np.asarray(res.est_gains), np.asarray(m)


@settings(max_examples=12, deadline=None)
@given(st.integers(min_value=0, max_value=len(_TUNE_MODELS) - 1),
       st.integers(min_value=1, max_value=len(_TUNE_OVERRIDES) - 1),
       st.sampled_from(["single", "serial"]))
def test_kernel_config_bit_identity(mi, ci, backend):
    """Property: any tuner-reachable RunSpec override produces seeds, gains,
    and a canonical matrix byte-identical to the hard-coded defaults, for
    every diffusion model on every always-available backend."""
    model = _TUNE_MODELS[mi]
    base_key = (model, backend)
    if base_key not in _tune_baselines:
        _tune_baselines[base_key] = _tune_run(model, backend, {})
    seeds0, gains0, m0 = _tune_baselines[base_key]
    seeds, gains, m = _tune_run(model, backend, _TUNE_OVERRIDES[ci])
    np.testing.assert_array_equal(seeds, seeds0)
    np.testing.assert_array_equal(gains, gains0)
    np.testing.assert_array_equal(m, m0)

    if backend == "serial" and _mesh_repair_ready():
        m_seeds, m_gains, m_m = _tune_run(model, "mesh", _TUNE_OVERRIDES[ci])
        np.testing.assert_array_equal(m_seeds, seeds0)
        np.testing.assert_array_equal(m_gains, gains0)
        np.testing.assert_array_equal(m_m, m0)
