"""Backend protocol + registry — one execution contract, three strategies.

A :class:`Backend` answers four questions about one (graph, :class:`RunSpec`)
pair:

  * ``supports(g, spec)``   — can I run this, and if not, why not;
  * ``find_seeds(...)``     — the full Alg. 4 seed-selection loop;
  * ``build_matrix(...)``   — Alg. 4 lines 3-6 only (fill + propagate to
    fixpoint), the half the :class:`~repro.service.store.SketchStore`
    amortizes; banks build through *any* registered backend because every
    backend returns the canonical (original-id row order, full-J column)
    ``int8`` matrix;
  * ``fixpoint(...)`` / ``cascade(...)`` — the two inner hooks (re-propagate
    an existing matrix / spread one committed seed) for repair-style callers
    holding a sound lower bound; plan-aware delta repair dispatches on the
    ``shard_repair`` capability and calls ``repair_plan_shards`` instead.

Results are backend-invariant by contract: the same (graph, sketch setting)
must produce bit-identical seed sets and matrices on every backend that
supports it. ``resolve_backend`` implements ``backend="auto"``:

  1. an explicit name is honored (and raises with the reason when that
     backend cannot run here);
  2. ``spec.num_shards <= 1`` and no mesh given -> ``single``;
  3. otherwise ``mesh`` if the device count allows it. Only on CPU does
     a grid too large for the devices run on ``serial`` instead (the exact
     ring schedule, one host — the test stand-in for a mesh); on an
     accelerator it raises :class:`BackendUnavailable` with the mesh's
     reason, so a sharded request never silently runs another path.
"""
from __future__ import annotations

import abc
import dataclasses
from typing import Dict, Optional, Tuple

import numpy as np

from repro.core.difuser import InfluenceResult
from repro.graphs.structs import Graph
from repro.runtime.spec import RunSpec


class BackendUnavailable(RuntimeError):
    """The requested backend cannot run in this environment/spec."""


def apply_tuning(g: Optional[Graph], spec: RunSpec,
                 backend_name: str) -> RunSpec:
    """The backends' tuning hook: overlay measured kernel-config winners
    onto ``spec`` per its ``tuning`` mode (see :mod:`repro.tune`).

    ``tuning="off"`` short-circuits here without importing the tuner —
    the historical zero-overhead path. Tuned fields are performance-only
    (tile shapes, scan chunks, ring schedule), so results are identical
    whichever spec comes back.
    """
    if getattr(spec, "tuning", "off") == "off" or g is None:
        return spec
    from repro.tune import resolve_spec

    return resolve_spec(g, spec, backend=backend_name)


@dataclasses.dataclass(frozen=True)
class BackendCapabilities:
    """What a backend reports about itself (the ``supports`` fast facts)."""

    name: str
    distributed: bool        # shards work across a (mu_v, mu_s) grid
    needs_mesh: bool         # requires a jax device mesh to run
    shard_repair: bool       # can re-propagate individual plan shards
    description: str = ""


@dataclasses.dataclass
class RunReport:
    """What a backend's ``find_seeds`` returns: the result plus provenance.

    ``result`` is the plain :class:`InfluenceResult` (identical across
    backends); ``partition`` is the built :class:`Partition2D` when the
    backend sharded the graph (``None`` on ``single``); ``wall_s`` is the
    end-to-end wall time including host partition builds.
    """

    result: InfluenceResult
    backend: str
    spec: RunSpec
    partition: Optional[object] = None
    wall_s: float = 0.0


class Backend(abc.ABC):
    """One execution strategy for the DiFuseR pipeline (see module doc)."""

    name: str = "?"

    @abc.abstractmethod
    def capabilities(self) -> BackendCapabilities:
        ...

    def available(self) -> Tuple[bool, str]:
        """Environment check only (jax version, device count...)."""
        return True, ""

    def supports(self, g: Optional[Graph], spec: RunSpec) -> Tuple[bool, str]:
        """Can this backend execute ``spec`` (optionally against ``g``)?"""
        return self.available()

    @abc.abstractmethod
    def find_seeds(self, g: Graph, k: int, spec: RunSpec, *,
                   x: Optional[np.ndarray] = None, mesh=None,
                   plan=None) -> RunReport:
        """Run the full Alg. 4 loop; seeds come back in original vertex ids."""

    @abc.abstractmethod
    def build_matrix(self, g: Graph, spec: RunSpec, x: np.ndarray, *,
                     reg_offset: int = 0, normalized: bool = False,
                     edges=None, mesh=None):
        """Fill + propagate-to-fixpoint; returns ``(matrix, iters)``.

        ``matrix`` is the canonical layout every backend agrees on:
        ``int8[g.n_pad, len(x)]`` with rows in original-id order (sharded
        backends un-permute before returning). ``reg_offset`` offsets the
        register hash slots (sample-space bank builds). ``normalized=True``
        promises ``g`` is dst-sorted and ``x`` canonical already. ``edges``
        passes precomputed ``(src, dst, h, lo, thr)`` device operands —
        an optimization hint only the ``single`` backend consumes. ``mesh``
        pins an explicit jax mesh — only the ``mesh`` backend consumes it.
        """

    def fixpoint(self, m, g: Graph, spec: RunSpec, x: np.ndarray, *,
                 edges=None):
        """Hook: re-propagate an existing canonical matrix to fixpoint.
        Returns ``(matrix, iters)``. Exposed for repair-style callers that
        hold a sound lower bound of the fixpoint (the plan-aware path goes
        through ``repair_plan_shards`` instead)."""
        raise NotImplementedError(f"backend {self.name!r} has no fixpoint hook")

    def cascade(self, m, seed_vertex: int, g: Graph, spec: RunSpec,
                x: np.ndarray, *, edges=None):
        """Hook: commit ``seed_vertex`` and spread its cascade to fixpoint.
        Returns ``(matrix, iters)``."""
        raise NotImplementedError(f"backend {self.name!r} has no cascade hook")

    def repair_plan_shards(self, g: Graph, spec: RunSpec, x: np.ndarray,
                           planned_m, plan, touched, *, mesh=None):
        """Shard-restricted repair of a plan-order matrix; returns
        ``(planned_matrix, sweeps, shards_swept)``. MUST be implemented by
        every backend whose ``capabilities().shard_repair`` is True —
        ``service.delta.apply_delta`` dispatches on that flag. ``mesh`` pins
        the jax mesh of a device-resident matrix (the entry's placement) —
        only the ``mesh`` backend consumes it."""
        raise NotImplementedError(
            f"backend {self.name!r} reports no shard_repair capability")

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"<{type(self).__name__} {self.name!r}>"


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_BACKENDS: Dict[str, Backend] = {}


def register_backend(backend: Backend, *, overwrite: bool = False) -> Backend:
    """Register a backend under ``backend.name`` (pluggable like the
    diffusion-model and partition-strategy registries)."""
    if backend.name in _BACKENDS and not overwrite:
        raise ValueError(f"backend {backend.name!r} already registered")
    _BACKENDS[backend.name] = backend
    return backend


def get_backend(name) -> Backend:
    """Resolve a backend by name (a Backend instance passes through)."""
    if isinstance(name, Backend):
        return name
    b = _BACKENDS.get(name)
    if b is None:
        raise KeyError(f"unknown backend {name!r}; registered: "
                       f"{sorted(_BACKENDS)} (plus 'auto')")
    return b


def available_backends() -> Dict[str, Tuple[bool, str]]:
    """name -> (available, reason-if-not) for every registered backend."""
    return {name: b.available() for name, b in sorted(_BACKENDS.items())}


def resolve_backend(spec: RunSpec, g: Optional[Graph] = None, *,
                    mesh=None) -> Backend:
    """Apply the ``backend="auto"`` rules (module doc) to pick a backend."""
    if spec.backend != "auto":
        b = get_backend(spec.backend)
        ok, why = b.supports(g, spec)
        if not ok:
            raise BackendUnavailable(
                f"backend {spec.backend!r} cannot run this spec: {why}")
        return b
    if mesh is None and spec.num_shards <= 1:
        return get_backend("single")
    b = get_backend("mesh")
    ok, why_mesh = b.supports(g, spec)
    if ok:
        return b
    import jax

    if jax.default_backend() != "cpu":
        raise BackendUnavailable(
            f"the spec asks for a {spec.mu_v} x {spec.mu_s} shard grid and "
            f"the mesh backend cannot run it here: {why_mesh}")
    serial = get_backend("serial")
    ok, why = serial.supports(g, spec)
    if not ok:
        # the fallback must also say *why* it cannot run (e.g. registers
        # not divisible by the sim grid) instead of failing mid-build
        raise BackendUnavailable(
            f"no backend can run this spec: mesh unavailable and the "
            f"serial fallback cannot either: {why}")
    return serial


def resolve_residency(spec: RunSpec, backend: Backend) -> str:
    """Apply the ``residency="auto"`` rule: banks live on the mesh exactly
    when the resolved backend runs there (``needs_mesh``) — serving
    reductions then happen where the registers already are — and on the host
    otherwise. An explicit ``"host"``/``"device"`` is honored as-is."""
    if spec.residency != "auto":
        return spec.residency
    return "device" if backend.capabilities().needs_mesh else "host"
