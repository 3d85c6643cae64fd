"""InfluenceSession — one facade over the whole influence pipeline.

Before the runtime layer, a caller stitched four APIs by hand:
``core.difuser.find_seeds`` (cold), ``core.difuser.find_seeds_warm`` +
``build_sketch_matrix`` (amortized), ``SketchStore.get_or_build`` (resident
index), and one of three executors. A session binds a graph to a
:class:`RunSpec` once and exposes all of it behind a single object; the
backend is resolved lazily from the spec (``"auto"`` rules in
:mod:`repro.runtime.base`) so the same session code runs unchanged from one
device to a mesh.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from repro.core import difuser as _difuser
from repro.core.difuser import InfluenceResult
from repro.graphs.structs import Graph, GraphDelta
from repro.runtime.base import (Backend, BackendUnavailable, RunReport,
                                resolve_backend, resolve_residency)
from repro.runtime.spec import RunSpec
from repro.service.delta import DeltaReport, apply_delta
from repro.service.store import SketchStore, StoreEntry


class InfluenceSession:
    """A graph bound to one execution contract (:class:`RunSpec`).

    ``store`` shares a :class:`SketchStore` across sessions (multi-graph
    tenancy); by default the session owns a private one, built through the
    session's backend. ``mesh`` pins an explicit jax mesh for the ``mesh``
    backend's ``find_seeds``/``build_sketch_matrix`` (otherwise one is
    constructed from ``spec.mu_v x spec.mu_s``); store-path builds
    (``entry()``) always construct their own mesh from the spec, since a
    shared store outlives any one session's device placement.
    """

    def __init__(self, graph: Graph, spec: Optional[RunSpec] = None, *,
                 store: Optional[SketchStore] = None, mesh=None,
                 num_banks: int = 1):
        self.graph = graph
        self.spec = spec if spec is not None else RunSpec()
        self.mesh = mesh
        self.store = (store if store is not None
                      else SketchStore(num_banks=num_banks, spec=self.spec))
        self.last_report: Optional[RunReport] = None
        # the store key of this session's resident entry: store keys name the
        # *lineage* graph (they survive deltas), so the session pins the key
        # instead of re-deriving it from the (possibly post-delta) graph
        self._entry_key = None

    @property
    def backend(self) -> Backend:
        """The backend the spec resolves to *right now* (auto rules are
        environment-dependent: device count, jax version)."""
        return resolve_backend(self.spec, self.graph, mesh=self.mesh)

    # ------------------------------------------------------------------
    # Cold path
    # ------------------------------------------------------------------

    def find_seeds(self, k: int, *, x: Optional[np.ndarray] = None,
                   plan=None) -> InfluenceResult:
        """Full Alg. 4 through the resolved backend. Execution provenance
        (backend name, built partition, wall time) lands in
        ``self.last_report``."""
        report = self.backend.find_seeds(self.graph, k, self.spec, x=x,
                                         mesh=self.mesh, plan=plan)
        self.last_report = report
        return report.result

    def build_sketch_matrix(self, *, x: Optional[np.ndarray] = None,
                            reg_offset: int = 0):
        """Alg. 4 lines 3-6 through the resolved backend: returns
        ``(matrix, iters, x_used)`` in the canonical layout (identical
        across backends)."""
        cfg = self.spec.difuser_config()
        g, x_norm = _difuser.normalize_inputs(self.graph, cfg, x)
        m, iters = self.backend.build_matrix(g, self.spec, x_norm,
                                             reg_offset=reg_offset,
                                             normalized=True, mesh=self.mesh)
        return m, iters, x_norm

    # ------------------------------------------------------------------
    # Warm / resident path (the store half of the facade)
    # ------------------------------------------------------------------

    def entry(self, *, x: Optional[np.ndarray] = None) -> StoreEntry:
        """The resident store entry for this session's (graph, setting),
        built through the session's backend on first demand — and *placed*
        per the spec's residency: ``residency="device"`` (or ``"auto"``
        resolving to the mesh backend) pins the banks as plan-order row
        blocks on the serving mesh, so queries reduce shard-local."""
        if (x is None and self._entry_key is not None
                and self._entry_key in self.store):
            e = self.store.entry(self._entry_key)
        else:
            e = self.store.get_or_build(self.graph,
                                        self.spec.difuser_config(), x)
            self._entry_key = e.key
        self._route_residency(e)
        return e

    def _route_residency(self, e: StoreEntry) -> None:
        """Place a host-order entry on the mesh when the spec asks for (or
        auto-resolves to) device residency; attach a serving plan first if
        the entry has none (``spec.partition`` strategy, one row block per
        shard of the spec's grid)."""
        backend = self.backend
        if resolve_residency(self.spec, backend) != "device":
            return
        if e.residency == "device":
            return
        shards = (e.plan.mu_v if e.plan is not None
                  else max(self.spec.mu_v if self.spec.mu_v > 1
                           else self.spec.num_shards, 1))
        import jax

        if len(jax.devices()) < shards:
            raise BackendUnavailable(
                f"device residency places {shards} row blocks but only "
                f"{len(jax.devices())} device(s) are visible (export "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={shards} "
                f"for a host-device mesh); residency='host' serves the same "
                f"answers host-order")
        if e.plan is None:
            from repro.partition import plan_partition

            plan = plan_partition(e.graph, shards, mu_s=1,
                                  strategy=self.spec.partition, x=e.x,
                                  seed=e.cfg.seed, model=e.cfg.model)
            self.store.attach_plan(e.key, plan)
        e.place_on_mesh(self._serving_mesh(e.plan),
                        vertex_axis=self.spec.vertex_axis)

    def _serving_mesh(self, plan):
        """The session's pinned mesh when it matches the plan's row-only
        serving layout, else a fresh ``(mu_v, 1)`` mesh."""
        import math

        if (self.mesh is not None
                and self.mesh.shape.get(self.spec.vertex_axis) == plan.mu_v
                and math.prod(self.mesh.shape.values()) == plan.mu_v):
            return self.mesh
        from repro.launch.mesh import make_serving_mesh

        return make_serving_mesh(plan.mu_v, vertex_axis=self.spec.vertex_axis,
                                 sim_axis=self.spec.sim_axes[0])

    def find_seeds_warm(self, k: int, *,
                        x: Optional[np.ndarray] = None) -> InfluenceResult:
        """K seed rounds from the resident matrix (cold build amortized
        away). The round program is the identical trace as the cold path's,
        so warm seeds are byte-identical to ``find_seeds`` regardless of
        which backend built the matrix — a device-resident entry runs the
        rounds under shard_map straight off its placed row blocks. Routed
        through ``queries.top_k_seeds`` so a stale entry (removal deltas
        below the rebuild threshold) is lazily rebuilt first, exactly like
        engine-served TopKSeeds — warm never serves an unsound index."""
        from repro.service.queries import top_k_seeds

        return top_k_seeds(self.store, self.entry(x=x), k)

    def apply_delta(self, delta: GraphDelta, *,
                    staleness_threshold: float = 0.1) -> DeltaReport:
        """Apply a graph delta to the resident entry through the session's
        backend: on a shard-repair-capable backend (``serial``, or ``mesh``
        for device-resident banks) with a plan attached, insertions
        re-propagate only the plan shards the delta dirtied. The session's
        own graph follows the entry's post-delta graph, so the cold paths
        (``find_seeds``, ``build_sketch_matrix``) and the warm/resident
        paths keep answering about the same graph."""
        e = self.entry()
        report = apply_delta(self.store, e.key, delta,
                             staleness_threshold=staleness_threshold,
                             backend=self.backend)
        self.graph = self.store.entry(e.key).graph
        return report
