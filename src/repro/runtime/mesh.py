"""``mesh`` backend — the shard_map 2-D distributed runtime.

Wraps ``core/distributed.py``. Needs at least ``mu_v * mu_s`` devices;
otherwise ``supports`` says no, and ``auto`` resolution raises on an
accelerator (on CPU it runs the ``serial`` backend, which executes the exact
same ring schedule — results are bit-identical by contract).
"""
from __future__ import annotations

import functools
import math
import time
from typing import Optional

import numpy as np

from repro.graphs.structs import Graph
from repro.runtime.base import (Backend, BackendCapabilities, RunReport,
                                apply_tuning, register_backend)
from repro.runtime.spec import RunSpec


class MeshBackend(Backend):
    name = "mesh"

    @functools.cached_property
    def partitions(self):
        """Each graph's partition, placed on its mesh, and the programs
        built on its shapes: built by the first job or build, reused by
        every later call whose x falls inside its value ranges."""
        from repro.core.distributed import PartitionCache

        return PartitionCache()

    def capabilities(self) -> BackendCapabilities:
        return BackendCapabilities(
            name=self.name, distributed=True, needs_mesh=True,
            shard_repair=True,
            description="shard_map 2-D runtime (ring/allgather schedules; "
                        "shard-restricted repair of device-resident banks)")

    def supports(self, g, spec: RunSpec):
        import jax

        ndev = len(jax.devices())
        if ndev < spec.num_shards:
            return False, (f"spec asks for {spec.num_shards} shards but only "
                           f"{ndev} device(s) are visible (export XLA_FLAGS="
                           f"--xla_force_host_platform_device_count="
                           f"{spec.num_shards} for a host-device mesh)")
        if spec.num_registers % max(spec.mu_s, 1) != 0:
            return False, (f"num_registers={spec.num_registers} not divisible "
                           f"by mu_s={spec.mu_s}")
        return True, ""

    def _mesh_for(self, spec: RunSpec, mesh=None):
        if mesh is not None:
            return mesh
        from repro.launch.mesh import make_mesh

        mu_v, mu_s = max(spec.mu_v, 1), max(spec.mu_s, 1)
        if len(spec.sim_axes) != 1:
            raise ValueError("pass an explicit mesh for multi-sim-axis specs")
        return make_mesh((mu_v, mu_s), (spec.vertex_axis, spec.sim_axes[0]))

    def _check(self, g, spec: RunSpec):
        ok, why = self.supports(g, spec)
        if not ok:
            from repro.runtime.base import BackendUnavailable

            raise BackendUnavailable(f"mesh backend: {why}")

    def find_seeds(self, g: Graph, k: int, spec: RunSpec, *,
                   x: Optional[np.ndarray] = None, mesh=None,
                   plan=None) -> RunReport:
        self._check(g, spec)
        from repro.core import distributed as _dist

        mesh = self._mesh_for(spec, mesh)
        t0 = time.perf_counter()
        # tuned on the serial ring twin — same bucket schedule, so the
        # (local_sweeps, pad_mode) ranking transfers to the device path
        spec = apply_tuning(g, spec, self.name)
        cfg = spec.distributed_config()
        res, part = _dist._find_seeds_distributed(
            g, k, mesh, cfg, x, plan=plan, partitions=self.partitions)
        return RunReport(result=res, backend=self.name, spec=spec,
                         partition=part, wall_s=time.perf_counter() - t0)

    def build_matrix(self, g: Graph, spec: RunSpec, x: np.ndarray, *,
                     reg_offset: int = 0, normalized: bool = False,
                     edges=None, mesh=None):
        # ``edges`` (single-backend device operands) is not applicable: the
        # shard_map build sweeps the graph's partition, bucketed once and
        # resident on the mesh (an x outside its value ranges buckets its
        # own, for that call alone).
        self._check(g, spec)
        from repro.core import distributed as _dist

        spec = apply_tuning(g, spec, self.name)
        cfg = spec.distributed_config()
        if not normalized:
            from repro.core.difuser import normalize_inputs

            g, x = normalize_inputs(g, spec.difuser_config(), x)
        mesh = self._mesh_for(spec, mesh)
        mu_s = math.prod(mesh.shape[ax] for ax in cfg.sim_axes)
        if x is not None and np.asarray(x).shape[0] % mu_s != 0:
            raise ValueError(
                f"bank of {np.asarray(x).shape[0]} registers not divisible "
                f"by the mesh's {mu_s} sim shard(s)")
        m, iters, _ = _dist.build_matrix_distributed(
            g, mesh, cfg, x, reg_offset=reg_offset,
            partitions=self.partitions)
        return m, iters

    # -- shard-level repair (device-resident store banks) ------------------

    def repair_plan_shards(self, g: Graph, spec: RunSpec, x: np.ndarray,
                           planned_m, plan, touched, *, mesh=None):
        """Frontier-restricted re-propagation of only the touched plan
        shards under shard_map (``core.distributed.
        repair_plan_shards_distributed``) — the device twin of the serial
        ring repair, bit-identical to it and to a full rebuild. ``mesh``
        should be the placement mesh of the matrix (a device-resident
        entry's); without one, a row-only serving mesh of ``plan.mu_v``
        devices is constructed."""
        from repro.core import distributed as _dist

        if mesh is None:
            from repro.launch.mesh import make_serving_mesh

            mesh = make_serving_mesh(plan.mu_v, vertex_axis=spec.vertex_axis)
        sim_axes = tuple(ax for ax in mesh.axis_names if ax != spec.vertex_axis)
        cfg = spec.with_(sim_axes=sim_axes).distributed_config()
        return _dist.repair_plan_shards_distributed(
            g, mesh, cfg, x, planned_m, plan, touched)


register_backend(MeshBackend())
