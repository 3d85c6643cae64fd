"""Pallas kernel: per-vertex cardinality sufficient statistics.

For seed selection every vertex needs its estimator statistics
(sum_j 2^-M[u,j] over valid registers, and the valid count). These are the
shard-local *additive* halves of the harmonic-mean estimator (paper eq. (7)
/ Fig. 3): shards psum them and finish the estimate replicated.

TPU tiling: grid over vertex blocks; each step reads a (VERTEX_BLOCK, J)
int8 pane (J <= 1024 -> <=256 KiB VMEM) and reduces along lanes into two
(VERTEX_BLOCK, 1) float32 columns. Register-dim reduction = lane reduction,
the cheap direction on the VPU. The vertex axis is padded up to a whole
block with VISITED rows (zero statistics, sliced off).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (ROW_TILE, VERTEX_BLOCK, VISITED,
                                  clamp_block, pad_rows, pallas_call,
                                  round_up)


def _cardinality_kernel(m_ref, stat_ref, count_ref):
    m = m_ref[...].astype(jnp.int32)
    valid = m != VISITED
    mf = m.astype(jnp.float32)
    stat_ref[...] = jnp.sum(jnp.where(valid, jnp.exp2(-mf), 0.0), axis=-1,
                            keepdims=True)
    count_ref[...] = jnp.sum(valid.astype(jnp.float32), axis=-1, keepdims=True)


@partial(jax.jit, static_argnames=("vertex_block",))
def cardinality_stats_pallas(m, *, vertex_block: int = VERTEX_BLOCK):
    n_pad, num_regs = m.shape
    vertex_block = round_up(clamp_block(n_pad, vertex_block), ROW_TILE)
    m = pad_rows(m, round_up(n_pad, vertex_block))
    rows = m.shape[0]
    col = jax.ShapeDtypeStruct((rows, 1), jnp.float32)
    stat, count = pallas_call(
        _cardinality_kernel,
        grid=(rows // vertex_block,),
        in_specs=[pl.BlockSpec((vertex_block, num_regs), lambda v: (v, 0))],
        out_specs=[
            pl.BlockSpec((vertex_block, 1), lambda v: (v, 0)),
            pl.BlockSpec((vertex_block, 1), lambda v: (v, 0)),
        ],
        out_shape=[col, col],
    )(m)
    return stat[:n_pad, 0], count[:n_pad, 0]
