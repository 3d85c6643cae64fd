"""Device-resident sharded serving (ISSUE 5).

Two tiers:

* **always-on** — the shard-local reduction *math* is exercised without a
  mesh: ``queries.shard_partial_rows`` is called per shard on slices of the
  plan-order matrix and combined with ``np.maximum`` (the pmax twin); the
  result must be bit-identical to the host-order reductions. Plus routing
  units: ``QueryResult.backend`` accounting, ``apply_delta(backend="auto")``
  on host entries, placement preconditions, npz residency field.

* **mesh** — real ``NamedSharding`` placement on a host-device mesh: all
  four query classes bit-identical device vs host, mesh
  ``repair_plan_shards`` == serial repair == full rebuild, session
  residency routing, snapshot round-trip onto a mesh. These need
  ``MU_V`` devices and run in CI's 8-fake-device pass.
"""
import numpy as np
import pytest

from repro.core import sketch
from repro.core.difuser import DiFuserConfig
from repro.graphs import rmat_graph
from repro.graphs.structs import GraphDelta
from repro.partition import plan_partition
from repro.service import (CoverageProbe, InfluenceEngine, MarginalGain,
                           SketchStore, SpreadEstimate, TopKSeeds, apply_delta,
                           summarize_latencies)
from repro.service import queries as Q

MU_V = 4


def _mesh_ready(mu_v=MU_V):
    import jax

    if len(jax.devices()) < mu_v:
        return False, (f"needs {mu_v} devices (export XLA_FLAGS="
                       f"--xla_force_host_platform_device_count=8)")
    return True, ""


def _require_mesh():
    ok, why = _mesh_ready()
    if not ok:
        pytest.skip(why)


def _store_with_plan(strategy="degree", registers=128, seed=3, model="wc"):
    g = rmat_graph(7, edge_factor=6, seed=9, setting="w1")
    cfg = DiFuserConfig(num_registers=registers, seed=seed, model=model)
    store = SketchStore()
    e = store.get_or_build(g, cfg)
    plan = plan_partition(e.graph, MU_V, mu_s=1, strategy=strategy, x=e.x,
                          seed=seed, model=model)
    store.attach_plan(e.key, plan)
    return store, e


def _rng_sets(n, count, rng, max_len=6):
    return [tuple(int(v) for v in rng.integers(0, n, rng.integers(1, max_len)))
            for _ in range(count)]


# ---------------------------------------------------------------------------
# Always-on: the shard-local partial reduction is bit-identical to host order
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("estimator", ["hll", "fm_mean"])
@pytest.mark.parametrize("strategy", ["block", "degree", "random"])
def test_shard_partial_reduction_matches_host_bitwise(strategy, estimator):
    """Emulate the shard_map spread/probe bodies shard by shard (the exact
    ``shard_partial_rows`` function the device path runs, combined with the
    numpy twin of the pmax) and require bitwise equality with the host
    lowering — the core claim that lets device serving skip the gather."""
    g = rmat_graph(7, edge_factor=6, seed=9, setting="w1")
    cfg = DiFuserConfig(num_registers=128, seed=3, estimator=estimator)
    store = SketchStore()
    e = store.get_or_build(g, cfg)
    plan = plan_partition(e.graph, MU_V, mu_s=1, strategy=strategy, x=e.x,
                          seed=3)
    store.attach_plan(e.key, plan)

    rng = np.random.default_rng(11)
    sets = _rng_sets(e.graph.n, 16, rng)
    host_est = Q.spread_estimates(e, sets)

    # device-twin: per-shard partial merge over the plan-order rows + pmax.
    # The int8 register merge must match the host merge BITWISE — that is
    # the decomposition the device path rests on (pmax of the owned-row
    # partials == the host union). The float estimator tail is compared to
    # near-ulp here because this twin runs it eagerly while the host kernel
    # is one fused jit; the jit-vs-jit exactness is asserted by the guarded
    # test_device_queries_bit_identical_to_host below.
    planned = np.asarray(e.planned_matrix())
    cands = Q.pad_candidate_sets(sets, e.graph.n_pad - 1,
                                 max(len(s) for s in sets))
    rows = plan.perm[cands.astype(np.int64)].astype(np.int32)
    n_loc = plan.n_loc
    partials = []
    for v in range(MU_V):
        m_loc = planned[v * n_loc:(v + 1) * n_loc]
        part = np.asarray(Q.shard_partial_rows(m_loc, rows, v * n_loc, n_loc))
        partials.append(part.max(axis=1))                  # (B, J) partial
    merged = np.maximum.reduce(partials)                   # the pmax combine
    host_merged = np.asarray(e.matrix)[cands].max(axis=1)
    np.testing.assert_array_equal(merged, host_merged)
    sums = sketch.partial_sums(merged, estimator=estimator)
    twin_est = np.asarray(sketch.estimate_from_sums(
        sums, e.x.shape[0], estimator=estimator))
    np.testing.assert_allclose(twin_est, host_est, rtol=1e-6)

    # probe twin: single-row gather, same combine — registers again bitwise
    verts = np.arange(0, e.graph.n, 7, dtype=np.int32)
    host_probe, host_maxreg = Q.coverage_probes(e, verts)
    vrows = plan.perm[verts.astype(np.int64)].astype(np.int32)
    prow = np.maximum.reduce([
        np.asarray(Q.shard_partial_rows(planned[v * n_loc:(v + 1) * n_loc],
                                        vrows, v * n_loc, n_loc))
        for v in range(MU_V)])
    np.testing.assert_array_equal(prow, np.asarray(e.matrix)[verts])
    sums = sketch.partial_sums(prow, estimator=estimator)
    np.testing.assert_allclose(
        np.asarray(sketch.estimate_from_sums(sums, e.x.shape[0],
                                             estimator=estimator)),
        host_probe, rtol=1e-6)
    np.testing.assert_array_equal(prow.max(axis=-1).astype(np.int32),
                                  host_maxreg)


def test_planned_rows_partition_every_vertex_once():
    """Every original vertex id maps to exactly one shard-local row — the
    ownership property the VISITED-elsewhere gather relies on."""
    _, e = _store_with_plan()
    rows = Q._plan_rows(e, np.arange(e.plan.n_pad))
    assert sorted(rows.tolist()) == list(range(e.plan.n_pad))
    owners = rows // e.plan.n_loc
    assert np.bincount(owners, minlength=MU_V).sum() == e.plan.n_pad


# ---------------------------------------------------------------------------
# Always-on: engine accounting + delta routing + placement preconditions
# ---------------------------------------------------------------------------


def test_queryresult_records_backend_and_memo():
    store, e = _store_with_plan()
    engine = InfluenceEngine(store)
    key = e.key
    r1 = engine(key, SpreadEstimate((1, 2, 3)))
    assert r1.backend == "single:host"
    t1 = engine(key, TopKSeeds(3))
    t2 = engine(key, TopKSeeds(3))     # memo hit
    assert t1.backend == "single:host" and not t1.cache_hit
    assert t2.backend == "memo" and t2.cache_hit
    stats = summarize_latencies([r1, t1, t2])
    assert stats["by_backend"] == {"single:host": 2, "memo": 1}


def test_apply_delta_auto_routes_serial_on_host_entries():
    """backend='auto' on a host-resident planned entry picks the serial
    shard repair and stays bit-identical to a pristine rebuild."""
    store, e = _store_with_plan()
    rng = np.random.default_rng(5)
    add = rng.integers(0, e.graph.n, (6, 2))
    delta = GraphDelta.make(add=(add[:, 0], add[:, 1],
                                 np.full(6, 0.8, np.float32)))
    rep = apply_delta(store, e.key, delta, backend="auto")
    assert rep.repair_backend == "serial"
    assert rep.plan_shards_touched
    assert set(rep.plan_shards_touched) <= set(rep.shards_swept) or \
        rep.repair_sweeps == 0
    repaired = np.asarray(store.entry(e.key).matrix)
    store.rebuild(e.key)
    np.testing.assert_array_equal(repaired,
                                  np.asarray(store.entry(e.key).matrix))


def test_host_entries_never_repair_on_mesh():
    """Residency is authoritative over the caller's backend: a host-order
    planned entry repairs through serial even when the session's backend is
    mesh (shipping the matrix to a throwaway mesh helps nobody), and with
    no backend at all the historical per-bank repair keeps running."""
    from repro.runtime import get_backend
    from repro.service.delta import _shard_repair_backend

    _, e = _store_with_plan()
    assert _shard_repair_backend(get_backend("mesh"), e).name == "serial"
    assert _shard_repair_backend("mesh", e).name == "serial"
    assert _shard_repair_backend("auto", e).name == "serial"
    assert _shard_repair_backend(None, e) is None
    assert _shard_repair_backend("single", e) is None


def test_place_on_mesh_preconditions():
    g = rmat_graph(6, edge_factor=5, seed=1, setting="w1")
    store = SketchStore()
    e = store.get_or_build(g, DiFuserConfig(num_registers=64, seed=1))
    with pytest.raises(ValueError, match="plan"):
        e.place_on_mesh(mesh=None)
    assert e.residency == "host" and e.serving_backend == "single:host"
    # to_host on a host entry is a no-op
    assert e.to_host() is e


def test_npz_snapshot_carries_residency_field(tmp_path):
    store, e = _store_with_plan()
    path = str(tmp_path / "snap")
    store.save(path, e.key)
    z = np.load(path + ".npz")
    assert str(z["residency"]) == "host"
    restored = SketchStore().load(path)
    assert restored.residency == "host"
    np.testing.assert_array_equal(np.asarray(restored.matrix),
                                  np.asarray(e.matrix))


def test_runspec_residency_resolution():
    from repro.runtime import RunSpec, get_backend, resolve_residency

    assert RunSpec().residency == "auto"
    single = get_backend("single")
    serial = get_backend("serial")
    mesh = get_backend("mesh")
    assert resolve_residency(RunSpec(), single) == "host"
    assert resolve_residency(RunSpec(), serial) == "host"
    assert resolve_residency(RunSpec(), mesh) == "device"
    assert resolve_residency(RunSpec(residency="host"), mesh) == "host"
    assert resolve_residency(RunSpec(residency="device"), single) == "device"


# ---------------------------------------------------------------------------
# mesh: real placement on a host-device mesh
# ---------------------------------------------------------------------------


def _placed_store(strategy="degree", model="wc", registers=128):
    from repro.launch.mesh import make_serving_mesh

    store, e = _store_with_plan(strategy=strategy, model=model,
                                registers=registers)
    host = SketchStore()
    host_e = host.get_or_build(e.graph, e.cfg)     # untouched host twin
    e.place_on_mesh(make_serving_mesh(MU_V))
    return store, e, host, host_e


def test_placement_shards_rows_across_devices():
    _require_mesh()
    store, e, _, _ = _placed_store()
    assert e.residency == "device" and e.serving_backend == "mesh:device"
    pm = e.planned_matrix()
    assert pm.shape[0] == e.plan.n_pad
    devices = {s.device for s in pm.addressable_shards}
    assert len(devices) == MU_V
    for bank in e.banks:
        assert len({s.device for s in bank.addressable_shards}) == MU_V


@pytest.mark.parametrize("model", ["wc", "ic:0.2", "lt", "dic:0.5"])
def test_device_queries_bit_identical_to_host(model):
    _require_mesh()
    store, e, host, host_e = _placed_store(model=model)
    rng = np.random.default_rng(23)
    sets = _rng_sets(e.graph.n, 12, rng)
    np.testing.assert_array_equal(Q.spread_estimates(e, sets),
                                  Q.spread_estimates(host_e, sets))
    cands = [int(v) for v in rng.integers(0, e.graph.n, 8)]
    committed = _rng_sets(e.graph.n, 8, rng, max_len=4)
    np.testing.assert_array_equal(Q.marginal_gains(e, cands, committed),
                                  Q.marginal_gains(host_e, cands, committed))
    verts = [int(v) for v in rng.integers(0, e.graph.n, 16)]
    d_est, d_reg = Q.coverage_probes(e, verts)
    h_est, h_reg = Q.coverage_probes(host_e, verts)
    np.testing.assert_array_equal(d_est, h_est)
    np.testing.assert_array_equal(d_reg, h_reg)
    d_top = Q.top_k_seeds(store, e, 4)
    h_top = Q.top_k_seeds(host, host_e, 4)
    np.testing.assert_array_equal(d_top.seeds, h_top.seeds)
    np.testing.assert_array_equal(d_top.scores, h_top.scores)
    np.testing.assert_array_equal(d_top.est_gains, h_top.est_gains)


def test_engine_reports_device_backend():
    _require_mesh()
    store, e, _, _ = _placed_store()
    engine = InfluenceEngine(store)
    r = engine(e.key, CoverageProbe((0, 1, 2)))
    assert r.backend == "mesh:device"
    m = engine(e.key, MarginalGain(3, (1, 2)))
    assert m.backend == "mesh:device"


@pytest.mark.parametrize("strategy", ["block", "degree", "edge", "random"])
def test_mesh_repair_equals_serial_and_rebuild(strategy):
    _require_mesh()
    store, e, host, host_e = _placed_store(strategy=strategy)
    rng = np.random.default_rng(7)
    add = rng.integers(0, e.graph.n, (8, 2))
    delta = GraphDelta.make(add=(add[:, 0], add[:, 1],
                                 np.full(8, 0.7, np.float32)))

    rep_mesh = apply_delta(store, e.key, delta, backend="auto")
    assert rep_mesh.repair_backend == "mesh"
    assert store.entry(e.key).residency == "device"   # stayed placed

    host_plan = plan_partition(host_e.graph, MU_V, mu_s=1, strategy=strategy,
                               x=host_e.x, seed=3)
    host.attach_plan(host_e.key, host_plan)
    rep_serial = apply_delta(host, host_e.key, delta, backend="serial")
    assert rep_serial.repair_backend == "serial"

    mesh_m = np.asarray(store.entry(e.key).matrix)
    serial_m = np.asarray(host.entry(host_e.key).matrix)
    np.testing.assert_array_equal(mesh_m, serial_m)
    host.rebuild(host_e.key)
    np.testing.assert_array_equal(mesh_m,
                                  np.asarray(host.entry(host_e.key).matrix))
    assert rep_mesh.shards_swept == rep_serial.shards_swept
    assert rep_mesh.repair_sweeps == rep_serial.repair_sweeps


def test_session_auto_residency_and_repair_routing():
    _require_mesh()
    from repro.runtime import InfluenceSession, RunSpec

    g = rmat_graph(7, edge_factor=6, seed=9, setting="w1")
    spec = RunSpec(num_registers=128, seed=3, backend="mesh",
                   mu_v=2, mu_s=2, partition="degree")
    sess = InfluenceSession(g, spec)
    e = sess.entry()
    assert e.residency == "device"          # auto followed the mesh backend
    assert e.plan is not None and e.plan.mu_v == 2
    warm = sess.find_seeds_warm(4)
    cold = sess.find_seeds(4)
    np.testing.assert_array_equal(warm.seeds, cold.seeds)
    rng = np.random.default_rng(3)
    add = rng.integers(0, g.n, (4, 2))
    rep = sess.apply_delta(GraphDelta.make(
        add=(add[:, 0], add[:, 1], np.full(4, 0.9, np.float32))))
    assert rep.repair_backend == "mesh"


def test_snapshot_roundtrip_onto_mesh(tmp_path):
    _require_mesh()
    from repro.launch.mesh import make_serving_mesh

    store, e, _, _ = _placed_store()
    path = str(tmp_path / "devsnap")
    store.save(path, e.key)
    z = np.load(path + ".npz")
    assert str(z["residency"]) == "device"

    restored = SketchStore().load(path, mesh=make_serving_mesh(MU_V))
    assert restored.residency == "device"
    rng = np.random.default_rng(2)
    sets = _rng_sets(e.graph.n, 6, rng)
    np.testing.assert_array_equal(Q.spread_estimates(restored, sets),
                                  Q.spread_estimates(e, sets))
    # and a meshless load of the same snapshot degrades to host serving
    host_restored = SketchStore().load(path)
    assert host_restored.residency == "host"
    np.testing.assert_array_equal(Q.spread_estimates(host_restored, sets),
                                  Q.spread_estimates(e, sets))
