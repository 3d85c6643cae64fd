"""Unified runtime API: backend invariance, shims, sharded store/delta.

The contract under test (ISSUE 4 acceptance):

  * identical seed sets across the ``single`` / ``serial`` (/ ``mesh``,
    under the jax version guard) backends, for every registered diffusion
    model and every partition strategy;
  * the deprecated entry points (``find_seeds``,
    ``find_seeds_ring_serial``, ``find_seeds_distributed``) are thin shims
    over the facade and return byte-identical results while warning;
  * ``SketchStore`` banks build bit-identically through any registered
    backend;
  * a ``GraphDelta`` repair through the ``serial`` backend re-propagates
    only ``plan_shards_touched`` shards, bit-identical to a full rebuild.
"""
import numpy as np
import pytest

from repro.core.difuser import DiFuserConfig, find_seeds
from repro.graphs import rmat_graph
from repro.graphs.structs import Graph, GraphDelta
from repro.partition import find_seeds_ring_serial, plan_partition
from repro.runtime import (BackendUnavailable, InfluenceSession, RunSpec,
                           available_backends, get_backend, resolve_backend,
                           run)
from repro.service import SketchStore, apply_delta

MODELS = ["wc", "ic:0.2", "lt", "dic:0.5"]
STRATEGIES = ["block", "degree", "edge", "random"]


def _graph():
    return rmat_graph(7, edge_factor=6, seed=9, setting="w1")


def _spec(model="wc", **kw):
    return RunSpec(num_registers=128, seed=3, model=model, **kw)


_single_cache: dict = {}


def _single_result(model: str):
    """One single-backend reference run per model (shared across params)."""
    if model not in _single_cache:
        _single_cache[model] = run(_graph(), 4, _spec(model, backend="single"))
    return _single_cache[model].result


# ---------------------------------------------------------------------------
# Backend invariance: single == serial (== mesh) for all models x strategies
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_serial_backend_invariance(model, strategy):
    ref = _single_result(model)
    rep = run(_graph(), 4, _spec(model, backend="serial", mu_v=2, mu_s=2,
                                 partition=strategy))
    assert rep.backend == "serial"
    assert rep.partition is not None and rep.partition.plan.strategy == strategy
    np.testing.assert_array_equal(rep.result.seeds, ref.seeds)
    np.testing.assert_array_equal(rep.result.scores, ref.scores)
    np.testing.assert_array_equal(rep.result.est_gains, ref.est_gains)


@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_mesh_backend_invariance(model, strategy):
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("mesh backend needs >= 4 devices (export XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8)")
    ref = _single_result(model)
    rep = run(_graph(), 4, _spec(model, backend="mesh", mu_v=2, mu_s=2,
                                 partition=strategy))
    assert rep.backend == "mesh"
    np.testing.assert_array_equal(rep.result.seeds, ref.seeds)
    np.testing.assert_array_equal(rep.result.scores, ref.scores)


# ---------------------------------------------------------------------------
# auto resolution + registry
# ---------------------------------------------------------------------------


def test_auto_resolution_rules():
    g = _graph()
    assert resolve_backend(_spec(), g).name == "single"
    sharded = resolve_backend(_spec(mu_v=2, mu_s=2), g)
    import jax

    # too few devices for the mesh: the serial ring stands in on CPU only
    expect = "mesh" if len(jax.devices()) >= 4 else "serial"
    assert sharded.name == expect
    with pytest.raises(KeyError):
        get_backend("warp-drive")
    if len(jax.devices()) < 4:
        with pytest.raises(BackendUnavailable):
            resolve_backend(_spec(backend="mesh", mu_v=2, mu_s=2), g)


def test_auto_never_substitutes_serial_on_an_accelerator(monkeypatch):
    """Off CPU, a shard grid the devices cannot hold raises with the mesh's
    reason instead of quietly running the serial ring."""
    import jax

    g = _graph()
    too_many = len(jax.devices()) + 1
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(BackendUnavailable, match="device"):
        resolve_backend(_spec(mu_v=too_many, mu_s=1), g)
    assert resolve_backend(_spec(), g).name == "single"


def test_registry_reports_capabilities():
    caps = {name: get_backend(name).capabilities()
            for name in ("single", "serial", "mesh")}
    assert not caps["single"].distributed and not caps["single"].needs_mesh
    assert caps["serial"].distributed and caps["serial"].shard_repair
    assert caps["mesh"].needs_mesh and caps["mesh"].shard_repair
    avail = available_backends()
    assert avail["single"][0] and avail["serial"][0]


def test_session_reports_provenance():
    sess = InfluenceSession(_graph(), _spec(mu_v=2, mu_s=2, backend="serial",
                                            partition="degree"))
    res = sess.find_seeds(3)
    assert sess.last_report.backend == "serial"
    assert sess.last_report.wall_s > 0
    assert sess.last_report.partition.plan.strategy == "degree"
    assert res.seeds.shape == (3,)


# ---------------------------------------------------------------------------
# Deprecated entry points: thin shims, byte-identical through the facade
# ---------------------------------------------------------------------------


def test_shim_find_seeds_byte_identical():
    g = _graph()
    cfg = DiFuserConfig(num_registers=128, seed=3)
    with pytest.warns(DeprecationWarning, match="find_seeds is deprecated"):
        old = find_seeds(g, 4, cfg)
    new = InfluenceSession(g, RunSpec.from_config(cfg)).find_seeds(4)
    for f in ("seeds", "est_gains", "scores", "rebuilds", "x"):
        np.testing.assert_array_equal(getattr(old, f), getattr(new, f))
    assert old.propagate_iters == new.propagate_iters


def test_shim_find_seeds_ring_serial_byte_identical():
    g = _graph()
    cfg = DiFuserConfig(num_registers=128, seed=3)
    with pytest.warns(DeprecationWarning, match="find_seeds_ring_serial"):
        old, old_part = find_seeds_ring_serial(g, 4, cfg, mu_v=2, mu_s=2,
                                               strategy="degree")
    rep = run(g, 4, RunSpec.from_config(cfg, backend="serial", mu_v=2, mu_s=2,
                                        partition="degree"))
    for f in ("seeds", "est_gains", "scores", "rebuilds", "x"):
        np.testing.assert_array_equal(getattr(old, f), getattr(rep.result, f))
    assert old_part.mu_v == rep.partition.mu_v == 2


def test_shim_find_seeds_distributed_byte_identical():
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("mesh shim needs >= 4 devices")
    from repro.core.distributed import DistributedConfig, find_seeds_distributed
    from repro.launch.mesh import make_mesh

    g = _graph()
    cfg = DistributedConfig(num_registers=128, seed=3)
    mesh = make_mesh((2, 2), ("data", "model"))
    with pytest.warns(DeprecationWarning, match="find_seeds_distributed"):
        old, _ = find_seeds_distributed(g, 4, mesh, cfg)
    rep = run(g, 4, RunSpec.from_config(cfg, backend="mesh", mu_v=2, mu_s=2),
              mesh=mesh)
    np.testing.assert_array_equal(old.seeds, rep.result.seeds)
    np.testing.assert_array_equal(old.scores, rep.result.scores)


# ---------------------------------------------------------------------------
# Store banks through any backend + warm path
# ---------------------------------------------------------------------------


def test_store_banks_build_through_any_backend():
    g = _graph()
    cfg = DiFuserConfig(num_registers=128, seed=3)
    ref = np.asarray(SketchStore(num_banks=2).get_or_build(g, cfg).matrix)
    for spec in (RunSpec(mu_v=2, mu_s=1, partition="degree"),
                 RunSpec(mu_v=2, mu_s=2, partition="edge")):
        st = SketchStore(num_banks=2, backend="serial", spec=spec)
        m = np.asarray(st.get_or_build(g, cfg).matrix)
        np.testing.assert_array_equal(m, ref)
    import jax

    if len(jax.devices()) >= 2:
        st = SketchStore(num_banks=2, backend="mesh",
                         spec=RunSpec(mu_v=2, mu_s=1))
        m = np.asarray(st.get_or_build(g, cfg).matrix)
        np.testing.assert_array_equal(m, ref)


def test_session_warm_matches_cold_across_backends():
    g = _graph()
    for backend, grid in (("single", dict()),
                          ("serial", dict(mu_v=2, mu_s=2))):
        spec = _spec(backend=backend, **grid)
        sess = InfluenceSession(g, spec)
        cold = sess.find_seeds(4)
        warm = sess.find_seeds_warm(4)
        np.testing.assert_array_equal(cold.seeds, warm.seeds)
        np.testing.assert_array_equal(cold.scores, warm.scores)


def test_build_sketch_matrix_canonical_across_backends():
    g = _graph()
    m_single, _, x1 = InfluenceSession(g, _spec(backend="single")).build_sketch_matrix()
    m_serial, _, x2 = InfluenceSession(
        g, _spec(backend="serial", mu_v=2, mu_s=2,
                 partition="random")).build_sketch_matrix()
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
    np.testing.assert_array_equal(np.asarray(m_single), np.asarray(m_serial))


# ---------------------------------------------------------------------------
# GraphDelta repair through the serial backend (the acceptance criterion)
# ---------------------------------------------------------------------------


#: n=48 pads to n_pad=56, so a mu_v=2 block plan owns rows [0, 28) / [28, 56)
_CUT = 28
_N = 48


def _two_community_graph(seed: int = 4):
    """Two disconnected communities split at the block plan's shard boundary
    (``_CUT == n_loc``): community A on ids [0, 28) lands in shard 0,
    community B on [28, 48) in shard 1, so a delta inside one community must
    repair exactly one plan shard."""
    rng = np.random.default_rng(seed)
    m_half = _N * 4
    a_src = rng.integers(0, _CUT, m_half)
    a_dst = rng.integers(0, _CUT, m_half)
    b_src = rng.integers(_CUT, _N, m_half)
    b_dst = rng.integers(_CUT, _N, m_half)
    src = np.concatenate([a_src, b_src])
    dst = np.concatenate([a_dst, b_dst])
    w = np.full(src.shape[0], 0.35, dtype=np.float32)
    g = Graph.from_edges(_N, src, dst, w)
    assert g.n_pad == 2 * _CUT, "padding layout moved; realign _CUT"
    return g


def _delta(src, dst):
    return GraphDelta(
        add_src=np.asarray(src, np.int64), add_dst=np.asarray(dst, np.int64),
        add_weight=np.full(len(src), 0.9, np.float32),
        rem_src=np.zeros(0, np.int64), rem_dst=np.zeros(0, np.int64))


def _store_with_plan(g, cfg, mu_v=2):
    store = SketchStore()
    entry = store.get_or_build(g, cfg)
    plan = plan_partition(entry.graph, mu_v, mu_s=1, strategy="block",
                          x=entry.x, seed=cfg.seed)
    store.attach_plan(entry.key, plan)
    return store, entry


def test_delta_repair_serial_backend_touches_only_dirty_shards():
    g = _two_community_graph()
    cfg = DiFuserConfig(num_registers=128, seed=3)
    # delta strictly inside community B -> plan shard 1 only
    delta = _delta([_CUT + 1, _CUT + 3], [_CUT + 5, _CUT + 2])

    store, entry = _store_with_plan(g, cfg)
    rep = apply_delta(store, entry.key, delta, backend="serial")
    assert rep.repair_backend == "serial"
    assert rep.plan_shards_touched == (1,)
    # only the dirtied shard re-propagated: the communities are disconnected,
    # so the restricted sweeps can never escape shard 1
    assert rep.shards_swept == (1,)
    assert rep.repair_sweeps > 0 and not rep.rebuilt
    m_repaired = np.asarray(store.entry(entry.key).matrix)

    # bit-identical to a full pristine rebuild of the post-delta graph
    ref_store = SketchStore()
    m_rebuild = np.asarray(
        ref_store.get_or_build(entry.graph, cfg).matrix)
    np.testing.assert_array_equal(m_repaired, m_rebuild)

    # and to the historical per-bank single-device repair
    store2, entry2 = _store_with_plan(g, cfg)
    apply_delta(store2, entry2.key, delta)   # backend=None -> legacy path
    np.testing.assert_array_equal(
        m_repaired, np.asarray(store2.entry(entry2.key).matrix))


def test_delta_repair_serial_backend_spreads_when_it_must():
    """A cross-community delta dirties both shards; the repair still matches
    the rebuild bit-for-bit."""
    g = _two_community_graph()
    cfg = DiFuserConfig(num_registers=128, seed=3)
    delta = _delta([1], [_CUT + 7])          # A -> B bridge edge

    store, entry = _store_with_plan(g, cfg)
    rep = apply_delta(store, entry.key, delta, backend="serial")
    assert set(rep.plan_shards_touched) == {0, 1}
    assert set(rep.shards_swept) >= set(rep.plan_shards_touched)
    m_repaired = np.asarray(store.entry(entry.key).matrix)
    m_rebuild = np.asarray(
        SketchStore().get_or_build(entry.graph, cfg).matrix)
    np.testing.assert_array_equal(m_repaired, m_rebuild)


def test_delta_repair_without_plan_falls_back_to_legacy():
    g = _two_community_graph()
    cfg = DiFuserConfig(num_registers=128, seed=3)
    store = SketchStore()
    entry = store.get_or_build(g, cfg)      # no plan attached
    rep = apply_delta(store, entry.key, _delta([2], [5]), backend="serial")
    assert rep.repair_backend == "single"   # graceful fallback
    assert rep.shards_swept == ()
    m_after = np.asarray(store.entry(entry.key).matrix)
    m_ref = np.asarray(SketchStore().get_or_build(entry.graph, cfg).matrix)
    np.testing.assert_array_equal(m_after, m_ref)


def test_session_apply_delta_routes_backend():
    g = _two_community_graph()
    spec = _spec(backend="serial", mu_v=2, mu_s=1)
    sess = InfluenceSession(g, spec)
    entry = sess.entry()
    plan = plan_partition(entry.graph, 2, mu_s=1, strategy="block",
                          x=entry.x, seed=spec.seed)
    sess.store.attach_plan(entry.key, plan)
    rep = sess.apply_delta(_delta([_CUT + 1], [_CUT + 9]))
    assert rep.repair_backend == "serial"
    assert rep.plan_shards_touched == (1,)
    # seeds after the delta still match a cold run on the post-delta graph
    post = run(entry.graph, 3, _spec(backend="single")).result
    warm = sess.find_seeds_warm(3)
    np.testing.assert_array_equal(post.seeds, warm.seeds)


# ---------------------------------------------------------------------------
# fixpoint / cascade backend hooks
# ---------------------------------------------------------------------------


def test_backend_hooks_fixpoint_and_cascade():
    from repro.core.difuser import normalize_inputs
    from repro.core.sketch import VISITED

    g = _graph()
    spec = _spec()
    gn, xn = normalize_inputs(g, spec.difuser_config())
    single = get_backend("single")
    m, _ = single.build_matrix(gn, spec, xn, normalized=True)

    # a propagated matrix is already at fixpoint: both hooks are no-ops
    m_fix, _ = single.fixpoint(m, gn, spec, xn)
    np.testing.assert_array_equal(np.asarray(m_fix), np.asarray(m))
    serial = get_backend("serial")
    m_fix2, _ = serial.fixpoint(np.asarray(m), gn,
                                spec.with_(mu_v=2, mu_s=2), xn)
    np.testing.assert_array_equal(np.asarray(m_fix2), np.asarray(m))

    # cascade: committing a seed floods its row (and matches the in-loop op)
    s = int(run(g, 1, spec).result.seeds[0])
    m_casc, _ = single.cascade(m, s, gn, spec, xn)
    assert (np.asarray(m_casc)[s] == VISITED).all()
    with pytest.raises(NotImplementedError):
        serial.cascade(np.asarray(m), s, gn, spec, xn)
    # shard_repair protocol: only capable backends implement it
    with pytest.raises(NotImplementedError):
        single.repair_plan_shards(gn, spec, xn, np.asarray(m), None, (0,))


# ---------------------------------------------------------------------------
# Sweep counters, job spans and named scopes
# ---------------------------------------------------------------------------


def test_sweep_counters_have_one_entry_per_round():
    res = run(_graph(), 4, _spec(backend="single")).result
    for counts in (res.cascade_iters, res.rebuild_iters):
        assert counts.shape == (4,) and counts.dtype == np.int32
    assert res.rebuilds.any() and not res.rebuilds.all()
    assert (res.rebuild_iters[~res.rebuilds] == 0).all()
    assert (res.rebuild_iters[res.rebuilds] > 0).all()
    assert (res.cascade_iters > 0).all()


def test_sweep_counters_warm_equal_cold():
    g = _graph()
    for backend, grid in (("single", dict()),
                          ("serial", dict(mu_v=2, mu_s=2))):
        sess = InfluenceSession(g, _spec(backend=backend, **grid))
        cold = sess.find_seeds(4)
        warm = sess.find_seeds_warm(4)
        np.testing.assert_array_equal(warm.cascade_iters, cold.cascade_iters)
        np.testing.assert_array_equal(warm.rebuild_iters, cold.rebuild_iters)


def test_sweep_counters_match_a_plain_round_loop():
    """Round by round, committing the job's seeds: the cascade and rebuild
    fixpoints, called one at a time, take the sweeps the job reports."""
    import jax.numpy as jnp

    from repro.core import difuser
    from repro.core.cascade import cascade_from_seed
    from repro.core.simulate import propagate_to_fixpoint
    from repro.core.sketch import count_visited
    from repro.kernels import ops

    g, k, spec = _graph(), 4, _spec(backend="single")
    cfg = spec.difuser_config()
    res = run(g, k, spec).result
    gn, xn = difuser.normalize_inputs(g, cfg)
    src, dst, h, lo, thr = difuser.edge_operands(gn, cfg)
    x = jnp.asarray(xn)
    kw = dict(seed=cfg.seed, impl=cfg.impl, edge_chunk=cfg.edge_chunk)
    m, build, _ = difuser.build_sketch_matrix(gn, cfg, xn, normalized=True)
    assert build == res.propagate_iters
    oldscore = np.float32(0.0)
    for i, s in enumerate(res.seeds):
        m, casc = cascade_from_seed(m, int(s), src, dst, thr, x, h, lo,
                                    max_iters=cfg.max_cascade_iters, **kw)
        assert int(casc) == res.cascade_iters[i]
        score = np.float32(int(count_visited(m, gn.n))) / np.float32(128)
        rel = (score - oldscore) / np.maximum(score, np.float32(1e-9))
        rebuilt = 0
        if rel > cfg.rebuild_threshold:
            m = ops.sketch_fill(m, reg_offset=0, seed=cfg.seed, impl=cfg.impl)
            m, rebuilt = propagate_to_fixpoint(
                m, src, dst, thr, x, h, lo,
                max_iters=cfg.max_propagate_iters, **kw)
            oldscore = score
        assert int(rebuilt) == res.rebuild_iters[i]


@pytest.mark.parametrize("strategy", ["block", "degree"])
def test_serial_ring_counts_the_sweeps_single_does(strategy):
    ref = _single_result("wc")
    res = run(_graph(), 4, _spec(backend="serial", mu_v=2, mu_s=2,
                                 partition=strategy)).result
    assert res.propagate_iters == ref.propagate_iters
    np.testing.assert_array_equal(res.cascade_iters, ref.cascade_iters)
    np.testing.assert_array_equal(res.rebuild_iters, ref.rebuild_iters)


MESH_COUNTERS = r"""
import json
import numpy as np
from repro.graphs import rmat_graph
from repro.obs import trace
from repro.runtime import RunSpec, run

g = rmat_graph(7, edge_factor=6, seed=9, setting="w1")
spec = RunSpec(num_registers=128, seed=3)
single = run(g, 4, spec.with_(backend="single")).result
rec = trace.get_recorder()
rec.start()
mesh = run(g, 4, spec.with_(backend="mesh", mu_v=2, mu_s=2)).result
rec.stop()
print(json.dumps({
    "single": [single.propagate_iters, single.cascade_iters.tolist(),
               single.rebuild_iters.tolist()],
    "mesh": [mesh.propagate_iters, mesh.cascade_iters.tolist(),
             mesh.rebuild_iters.tolist()],
    "counts": mesh.sweep_counts(),
    "spans": [[e["name"], e["parent"], e["id"], e["attrs"]]
              for e in rec.events()]}, default=str))
"""


def test_mesh_body_counts_the_sweeps_single_does():
    """The shard_map body on four CPU devices returns the single driver's
    counters, and the first job's span encloses the partition it builds."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    src = Path(__file__).resolve().parent.parent / "src"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(src),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", MESH_COUNTERS], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["mesh"] == out["single"]
    spans = {name: (parent, sid, attrs)
             for name, parent, sid, attrs in out["spans"]}
    job_parent, job_id, job_attrs = spans["mesh.find_seeds"]
    assert job_parent is None
    part_parent, part_id, part_attrs = spans["mesh.partition"]
    assert part_parent == job_id
    for name in ("partition.sample_edge_sets", "partition.plan",
                 "partition.build_buckets"):
        assert spans[name][0] == part_id, name
    assert part_attrs["bucket_slots"] > 0
    assert {k: job_attrs[k] for k in out["counts"]} == out["counts"]
    assert job_attrs["compiles"] >= 1
    assert job_attrs["partition_reused"] == 0
    assert job_attrs["partition_fallback"] == 0
    assert job_attrs["bucket_pad_share"] == part_attrs["bucket_pad_share"]


def test_job_span_carries_the_counters_and_compiles():
    import jax

    from repro.obs import metrics, trace

    g = _graph()
    # a register count no other test uses: the first job compiles
    sess = InfluenceSession(g, RunSpec(num_registers=72, seed=3,
                                       backend="single"))
    before = metrics.counter("jit.compiles").value
    jax.jit(lambda a: a * 3 + 1)(np.arange(5))
    assert metrics.counter("jit.compiles").value == before + 1
    rec = trace.get_recorder()
    rec.start()
    try:
        first = sess.find_seeds(3)
        second = sess.find_seeds(3)
    finally:
        rec.stop()
        events = rec.events()
        rec.clear()
    jobs = [e for e in events if e["name"] == "single.find_seeds"]
    assert len(jobs) == 2
    for job, res in zip(jobs, (first, second)):
        attrs = job["attrs"]
        assert {k: attrs[k] for k in res.sweep_counts()} == res.sweep_counts()
        kids = [e["name"] for e in events if e["parent"] == job["id"]]
        assert kids == ["entry.normalize_inputs", "entry.edge_operands"]
    assert jobs[0]["attrs"]["compiles"] >= 1
    assert jobs[1]["attrs"]["compiles"] == 0


def test_find_seeds_program_names_the_four_scopes():
    import jax.numpy as jnp

    from repro.core import difuser

    g = _graph()
    cfg = _spec().difuser_config()
    gn, xn = difuser.normalize_inputs(g, cfg)
    src, dst, h, lo, thr = difuser.edge_operands(gn, cfg)
    text = difuser._find_seeds_jit.lower(
        src, dst, h, lo, thr, jnp.asarray(xn), n_pad=gn.n_pad, k=2,
        n_real=gn.n, num_regs=128, seed=cfg.seed, estimator=cfg.estimator,
        impl=cfg.impl, edge_chunk=cfg.edge_chunk, max_prop=8, max_casc=8,
        rebuild_threshold=cfg.rebuild_threshold,
        predicate=difuser.resolve_model(cfg.model).predicate,
    ).as_text(debug_info=True)
    for scope in ("difuser.fixpoint", "difuser.select", "difuser.cascade",
                  "difuser.rebuild"):
        assert scope in text, scope


def _star_twin(g):
    """The bench harness's warm-up graph: the same (n, m), every real arc
    leaving vertex 0 with probability 1."""
    import dataclasses

    real = np.arange(g.m) < g.m_real
    return dataclasses.replace(
        g, src=np.where(real, 0, g.src).astype(g.src.dtype),
        dst=np.where(real, 1 + np.arange(g.m) % (g.n - 1),
                     g.dst).astype(g.dst.dtype),
        weight=np.where(real, 1.0, 0.0).astype(np.float32))


def test_find_seeds_lowers_to_one_program_for_a_graph_and_its_star_twin():
    """The job program depends on (n_pad, m) alone, not on the degrees the
    cascade's pull layout groups: a Kronecker graph and its star twin lower
    to the same program, so a warm-up on the twin leaves nothing to compile."""
    import jax.numpy as jnp

    from repro.core import difuser

    kron = rmat_graph(8, edge_factor=8, seed=4, setting="w1")
    cfg = _spec().difuser_config()
    texts, splits = [], []
    for g in (kron, _star_twin(kron)):
        gn, xn = difuser.normalize_inputs(g, cfg)
        src, dst, h, lo, thr = difuser.edge_operands(gn, cfg)
        texts.append(difuser._find_seeds_jit.lower(
            src, dst, h, lo, thr, jnp.asarray(xn), n_pad=gn.n_pad, k=2,
            n_real=gn.n, num_regs=128, seed=cfg.seed,
            estimator=cfg.estimator, impl=cfg.impl,
            edge_chunk=cfg.edge_chunk, max_prop=8, max_casc=8,
            rebuild_threshold=cfg.rebuild_threshold,
            predicate=difuser.resolve_model(cfg.model).predicate).as_text())
        splits.append(int(difuser.ops.cascade_layout(
            src, dst, thr, n_pad=gn.n_pad, h=h, lo=lo).split_vertices))
    assert splits[0] != splits[1]     # the two layouts group differently
    assert texts[0] == texts[1]


def test_cascade_layout_counters_equal_their_hand_count():
    """On a hand-built graph the job span and the warm rounds' span carry
    the pull layout's counters as counted by hand: vertices 1-4 have W,
    W + 1 and 2W + 3 in-arcs beside one of 1, so they take 1, 1, 2 and 3
    pieces of W slots, every other vertex one dead piece, and two of them
    are split."""
    from repro.kernels.ref import CASCADE_SLOT_WIDTH as W
    from repro.obs import trace

    degrees = (1, W, W + 1, 2 * W + 3)
    src = np.concatenate([np.arange(5, 5 + d) for d in degrees])
    dst = np.repeat([1, 2, 3, 4], degrees)
    g = Graph.from_edges(2 * W + 8, src, dst,
                         np.full(src.size, 0.5, np.float32), edge_block=1)
    assert g.m == sum(degrees)        # no padded arcs
    pieces = g.n_pad - 4 + 1 + 1 + 2 + 3
    want = {"cascade_pad_share": 1.0 - sum(degrees) / (pieces * W),
            "cascade_split_vertices": 2}
    sess = InfluenceSession(g, _spec(backend="single"))
    rec = trace.get_recorder()
    rec.start()
    try:
        sess.find_seeds(2)
        sess.find_seeds_warm(2)
    finally:
        rec.stop()
        events = rec.events()
        rec.clear()
    spans = {e["name"]: e["attrs"] for e in events}
    for got in (spans["single.find_seeds"], spans["single.warm_rounds"]):
        assert got["cascade_split_vertices"] == want["cascade_split_vertices"]
        assert got["cascade_pad_share"] == pytest.approx(
            want["cascade_pad_share"], rel=1e-6)
