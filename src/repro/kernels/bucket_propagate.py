"""Pallas kernel: bucketed SIMULATE sweep for the distributed 2-D runtime.

The distributed partition (core/distributed.py) pre-buckets edges by
(write-owner, ring step) and precomputes the per-edge predicate operands
(hash once instead of once per sweep — legal for every registered diffusion
model because h is sample-independent). At each ring step the device merges
its local accumulator rows with rows of the *remote* register block that
just arrived. This kernel is that merge:

    acc[w[i], j] <- max(acc[w[i], j], block[r[i], j])   if pred(h[i], lo[i], t[i], X_j)

Same Jacobi/TPU-lane layout as sketch_propagate (registers ride the 128
lanes; per-edge scalars come from an SMEM table; int8 rows are read and
written through their aligned tile; no atomics because max-merge is
idempotent). Three resident panes (block, acc, out) bound a shard at
262,144 rows for lane tile 128. ops-level dispatch: the jnp oracle is
``core.distributed._bucket_sweep_propagate``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.sampling import fused_predicate
from repro.kernels.common import (EDGE_BLOCK, REG_TILE, VISITED,
                                  check_pane_rows, clamp_block,
                                  compiler_params, edge_block_for, edge_mask,
                                  edge_table, edge_table_spec, pad_rows,
                                  pallas_call, pane_spec, read_row,
                                  update_row)
from repro.kernels.sketch_propagate import pad_register_axis

#: rows of the SMEM bucket table: write row, read row, h, lo, thr
_W, _R, _HLT = 0, 1, (2, 3, 4)


def _bucket_kernel(tab_ref, x_ref, block_ref, acc_ref, out_ref, *,
                   edge_block: int, predicate):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = acc_ref[...]

    x = x_ref[...]

    def body(i, carry):
        mask = edge_mask(tab_ref, i, _HLT, x, predicate)
        contrib = jnp.where(mask, read_row(block_ref, tab_ref[_R, i]), VISITED)
        update_row(out_ref, tab_ref[_W, i],
                   lambda cur: jnp.where(cur == VISITED, cur,
                                         jnp.maximum(cur, contrib)))
        return carry

    jax.lax.fori_loop(0, edge_block, body, 0)


@partial(jax.jit, static_argnames=("edge_block", "reg_tile", "predicate"))
def bucket_propagate_pallas(acc, block, h, w, r, t, x, lo=None, *,
                            edge_block: int = EDGE_BLOCK, reg_tile: int = REG_TILE,
                            predicate=None):
    """acc/block: int8[n_loc, J_loc]; h/w/r/t/lo: (B,) bucket arrays; x: (J_loc,)."""
    if lo is None:
        lo = jnp.zeros(t.shape, jnp.uint32)
    if predicate is None:
        predicate = fused_predicate
    n_loc, j_loc = acc.shape
    reg_tile = clamp_block(j_loc, reg_tile)
    rows = check_pane_rows("bucket_propagate", n_loc, 3, reg_tile)
    # the bucket axis is padded with predicate-dead edges (t=0 never fires)
    # and the register axis with VISITED columns — bit-identical, any shape
    eb = edge_block_for(h.shape[0], edge_block)
    tab = edge_table((w, r, h, lo, t), eb)
    x = x.astype(jnp.uint32)
    block, _ = pad_register_axis(block, x, reg_tile)
    acc, x = pad_register_axis(acc, x, reg_tile)
    jp = x.shape[0]
    out = pallas_call(
        partial(_bucket_kernel, edge_block=eb, predicate=predicate),
        grid=(jp // reg_tile, tab.shape[1] // eb),
        in_specs=[
            edge_table_spec(5, eb, lambda j, e: (0, e)),
            pl.BlockSpec((1, reg_tile), lambda j, e: (0, j)),
            pane_spec(rows, reg_tile, lambda j, e: (0, j)),
            pane_spec(rows, reg_tile, lambda j, e: (0, j)),
        ],
        out_specs=pane_spec(rows, reg_tile, lambda j, e: (0, j)),
        out_shape=jax.ShapeDtypeStruct((rows, jp), jnp.int8),
        compiler_params=compiler_params("parallel", "arbitrary"),
    )(tab, x[None, :], pad_rows(block, rows), pad_rows(acc, rows))
    return out[:n_loc, :j_loc]
