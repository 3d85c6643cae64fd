"""Pallas kernel: hash-based fused edge sampling (paper §2.2, eq. (2)).

Produces the (E, R) membership mask ``predicate(h_e, lo_e, thr_e, X_r)`` in
tiles — default predicate ``(X_r ^ h(u,v)) < thr_e``, the interval form for
the diffusion model zoo. This is the purely data-parallel hot loop of
DiFuseR — one XOR + subtract + one unsigned compare per (edge, sample) —
and maps 1:1 onto the TPU VPU: the sample/register axis rides the 128-wide
lane dimension, edges ride sublanes. No MXU, no reductions, no control flow.

TPU tiling:
  grid = (E / EDGE_BLOCK, R / REG_TILE)
  VMEM per step: the per-edge operands as one (EDGE_BLOCK, 3) int32 column
  block (h/lo/thr — src/dst are consumed by the hash precompute and never
  enter the kernel), x as a (1, REG_TILE) row, out EDGE_BLOCK x REG_TILE x
  1 B: trivially VMEM-resident; the grid is compute-bound on the VPU as
  intended.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (EDGE_BLOCK, REG_TILE, clamp_block,
                                  edge_block_for, edge_table, pallas_call)
from repro.kernels.ref import edge_args
from repro.kernels.sketch_propagate import pad_register_axis


def _fused_sample_kernel(tab_ref, x_ref, out_ref, *, predicate):
    h, lo, thr = (jax.lax.bitcast_convert_type(tab_ref[:, c:c + 1], jnp.uint32)
                  for c in range(3))
    mask = predicate(h, lo, thr, x_ref[...])
    out_ref[...] = mask.astype(jnp.uint8)


@partial(jax.jit, static_argnames=("seed", "edge_block", "reg_tile",
                                   "predicate"))
def fused_sample_pallas(src, dst, thr, x, h=None, lo=None, *, seed: int = 0,
                        edge_block: int = EDGE_BLOCK, reg_tile: int = REG_TILE,
                        predicate=None):
    h, lo, predicate = edge_args(src, dst, thr, h, lo, predicate, seed)
    num_edges = src.shape[0]
    num_regs = x.shape[0]
    eb = edge_block_for(num_edges, edge_block)
    reg_tile = clamp_block(num_regs, reg_tile)
    tab = edge_table((h, lo, thr), eb).T
    _, x = pad_register_axis(None, x.astype(jnp.uint32), reg_tile)
    edges_pad, regs_pad = tab.shape[0], x.shape[0]
    out = pallas_call(
        partial(_fused_sample_kernel, predicate=predicate),
        grid=(edges_pad // eb, regs_pad // reg_tile),
        in_specs=[
            pl.BlockSpec((eb, 3), lambda e, r: (e, 0)),
            pl.BlockSpec((1, reg_tile), lambda e, r: (0, r)),
        ],
        out_specs=pl.BlockSpec((eb, reg_tile), lambda e, r: (e, r)),
        out_shape=jax.ShapeDtypeStruct((edges_pad, regs_pad), jnp.uint8),
    )(tab, x[None, :])
    return out[:num_edges, :num_regs]
