"""Unified execution API for the DiFuseR reproduction.

One :class:`Backend` protocol, three registered implementations —

  * ``single`` — the jitted single-device Alg. 4 driver (reference
    numerics, always available);
  * ``serial`` — the serial-ring executor (the 2-D ring schedule on one
    host; always available; the only backend with per-shard repair);
  * ``mesh``   — the shard_map 2-D runtime (needs mu_v * mu_s devices)

— selected by :class:`RunSpec` (``backend="auto"`` picks the best available
strategy for the requested shard grid), behind one facade object,
:class:`InfluenceSession`. Results are backend-invariant by contract: the
same (graph, sketch setting) produces bit-identical seed sets and register
matrices on every backend that supports it (tests/test_runtime.py).

Quick start::

    from repro.runtime import InfluenceSession, RunSpec

    sess = InfluenceSession(graph, RunSpec(num_registers=512, model="ic"))
    cold = sess.find_seeds(10)          # resolved backend, cold run
    warm = sess.find_seeds_warm(10)     # resident-index path, byte-identical
    print(sess.last_report.backend)     # which backend "auto" picked

See docs/runtime.md for the protocol, the ``auto`` resolution rules, and
the migration table from the legacy entry points (which remain as thin
deprecation shims over this package).
"""
from __future__ import annotations

import warnings
from typing import Optional

import jax

from repro.obs import metrics
from repro.runtime.base import (Backend, BackendCapabilities,
                                BackendUnavailable, RunReport,
                                available_backends, get_backend,
                                register_backend, resolve_backend,
                                resolve_residency)
from repro.runtime.spec import RunSpec

# importing the implementations registers them
from repro.runtime import single as _single   # noqa: F401,E402
from repro.runtime import serial as _serial   # noqa: F401,E402
from repro.runtime import mesh as _mesh       # noqa: F401,E402

from repro.runtime.session import InfluenceSession  # noqa: E402

#: the event JAX records around every backend compile of a program not in
#: its in-memory cache (a persistent-cache load counts too)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _count_compile(event: str, duration: float, **_) -> None:
    if event == BACKEND_COMPILE_EVENT:
        metrics.counter("jit.compiles").inc()


# one listener per process: the job spans read ``jit.compiles`` around
# each call (their ``compiles`` attribute)
jax.monitoring.register_event_duration_secs_listener(_count_compile)


def run(g, k: int, spec: Optional[RunSpec] = None, *, x=None, mesh=None,
        plan=None) -> RunReport:
    """One-shot facade: resolve the backend for ``spec`` and run Alg. 4.

    The functional spelling of ``InfluenceSession(g, spec).find_seeds(k)``
    for callers that don't need the resident-store half of the session.
    """
    spec = spec if spec is not None else RunSpec()
    backend = resolve_backend(spec, g, mesh=mesh)
    return backend.find_seeds(g, k, spec, x=x, mesh=mesh, plan=plan)


def warn_deprecated(old: str, new: str) -> None:
    """The shared deprecation notice of the legacy entry-point shims."""
    warnings.warn(f"{old} is deprecated; use {new} (see docs/runtime.md "
                  f"migration table)", DeprecationWarning, stacklevel=3)


__all__ = [
    "Backend",
    "BackendCapabilities",
    "BackendUnavailable",
    "InfluenceSession",
    "RunReport",
    "RunSpec",
    "available_backends",
    "get_backend",
    "register_backend",
    "resolve_backend",
    "resolve_residency",
    "run",
    "warn_deprecated",
]
