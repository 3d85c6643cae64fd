"""jit'd dispatch wrappers over the Pallas kernels and their jnp oracles.

``impl`` selects the backend:
  * "ref"     — pure-jnp oracle (kernels/ref.py); the default everywhere.
  * "pallas"  — the Pallas kernel. It is Mosaic-compiled on a TPU and
                interpreted on CPU — chosen per lowering platform by
                ``kernels.common.pallas_call``, never by a switch here. A
                shape a kernel cannot take on the chip raises with the
                reason; nothing falls back to "ref" or the interpreter.

All wrappers take the padded fixed-shape arrays produced by repro.graphs.

Diffusion-model hook (shared by both backends): optional per-edge ``h``
(sample-independent hash) and ``lo`` (interval low endpoint) operands plus a
static ``predicate`` callable. Omitting them reproduces the legacy
weighted-cascade behaviour bit-for-bit — h is then hashed from (src, dst,
seed) on the fly and the predicate is the threshold compare.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.common import EDGE_BLOCK, REG_TILE
from repro.kernels.cascade_step import cascade_sweep_pallas
from repro.kernels.fused_sample import fused_sample_pallas
from repro.kernels.fused_sweep import fused_sweep_pallas
from repro.kernels.sketch_cardinality import cardinality_stats_pallas
from repro.kernels.sketch_fill import sketch_fill_pallas
from repro.kernels.sketch_propagate import propagate_sweep_pallas


def fused_sample(src, dst, thr, x, *, seed: int = 0, impl: str = "ref",
                 h=None, lo=None, predicate=None,
                 edge_block: int = 0, reg_tile: int = 0):
    if impl == "ref":
        return _ref.fused_sample_ref(src, dst, thr, x, h, lo, seed=seed,
                                     predicate=predicate)
    return fused_sample_pallas(src, dst, thr, x, h, lo, seed=seed,
                               predicate=predicate,
                               edge_block=edge_block or EDGE_BLOCK,
                               reg_tile=reg_tile or REG_TILE)


def sketch_fill(m, *, reg_offset: int = 0, seed: int = 0, impl: str = "ref"):
    if impl == "ref":
        return _ref.sketch_fill_ref(m, reg_offset=reg_offset, seed=seed)
    return sketch_fill_pallas(m, reg_offset=reg_offset, seed=seed)


def propagate_sweep(m, src, dst, thr, x, *, seed: int = 0, impl: str = "ref",
                    edge_chunk: int = 2048, h=None, lo=None, predicate=None,
                    edge_block: int = 0, reg_tile: int = 0):
    if impl == "ref":
        return _ref.propagate_sweep_ref(
            m, src, dst, thr, x, h, lo, seed=seed, predicate=predicate,
            edge_chunk=edge_chunk)
    return propagate_sweep_pallas(m, src, dst, thr, x, h, lo, seed=seed,
                                  predicate=predicate,
                                  edge_block=edge_block or EDGE_BLOCK,
                                  reg_tile=reg_tile or REG_TILE)


def fused_sweep(m, src, dst, thr, x, *, num_sweeps: int = 1, seed: int = 0,
                impl: str = "ref", edge_chunk: int = 2048, h=None, lo=None,
                predicate=None, lane_fill: int = 0, reg_tile: int = 0):
    """``num_sweeps`` propagate sweeps fused into one launch — bit-identical
    to ``num_sweeps`` calls of :func:`propagate_sweep` on the same operands.
    ``lane_fill`` is the register-slab width (0 = full width / library
    default); see kernels/fused_sweep.py for the VMEM residency argument."""
    if impl == "ref":
        return _ref.fused_sweep_ref(
            m, src, dst, thr, x, h, lo, num_sweeps=num_sweeps, seed=seed,
            predicate=predicate, edge_chunk=edge_chunk, lane_fill=lane_fill)
    return fused_sweep_pallas(m, src, dst, thr, x, h, lo, seed=seed,
                              num_sweeps=num_sweeps, predicate=predicate,
                              lane_tile=lane_fill or reg_tile or REG_TILE)


def cascade_layout(src, dst, thr, *, n_pad: int, seed: int = 0,
                   edge_chunk: int = 2048, h=None, lo=None):
    """The ref cascade sweep's pull layout of the arcs (any order), with
    chunks of ``edge_chunk`` slots; see ``kernels/ref.py::cascade_layout``."""
    h, lo, _ = _ref.edge_args(src, dst, thr, h, lo, None, seed)
    return _ref.cascade_layout(src, dst, thr, h, lo, n_pad=n_pad,
                               chunk=edge_chunk)


def cascade_sweep(m, src, dst, thr, x, *, seed: int = 0, impl: str = "ref",
                  edge_chunk: int = 2048, h=None, lo=None, predicate=None,
                  edge_block: int = 0, reg_tile: int = 0, layout=None):
    """One cascade sweep over edges in any order. ``layout`` (ref impl):
    the arcs' :func:`cascade_layout`, built once by a caller that sweeps
    many times and then the sweep's only edge input (the arcs are not
    read); built here from the arcs when omitted."""
    if impl == "ref":
        if layout is None:
            layout = cascade_layout(src, dst, thr, n_pad=m.shape[0], seed=seed,
                                    edge_chunk=edge_chunk, h=h, lo=lo)
        return _ref.cascade_sweep_ref(m, layout, x, predicate=predicate)
    return cascade_sweep_pallas(m, src, dst, thr, x, h, lo, seed=seed,
                                predicate=predicate,
                                edge_block=edge_block or EDGE_BLOCK,
                                reg_tile=reg_tile or REG_TILE)


def cardinality_stats(m, *, impl: str = "ref"):
    if impl == "ref":
        stat, count = _ref.cardinality_stats_ref(m)
    else:
        stat, count = cardinality_stats_pallas(m)
    return jnp.stack([stat, count])
