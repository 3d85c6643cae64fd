"""Pallas kernel: one SIMULATE sweep (paper Alg. 2) — the core hot loop.

Pull-based sketch max-merge with sampling fused into the traversal:
for every edge (u, v) and register j whose fused predicate fires
(default ``(X_j ^ h(u,v)) < thr_uv``), ``M[u, j] <- max(M[u, j], M[v, j])``,
with VISITED (-1) sticky.

TPU adaptation of the CUDA kernel (see DESIGN.md §2):
  * registers ride the 128-lane dimension — one vector op covers 128
    simulations of one edge (the paper's warp = 32 threads becomes a lane
    tile = 128);
  * the warp-divergence problem becomes masked lanes; FASST raises lane
    occupancy exactly as it raises warp fill;
  * atomics are unnecessary because max-merge is idempotent (the paper's
    argument); duplicate-destination writes within an edge block are
    serialized by the in-kernel edge loop instead.

Grid = (J / REG_TILE, E / EDGE_BLOCK): the register tile is the outer
(major) axis so the (n_pad x REG_TILE) register panes for input and
accumulator stay VMEM-resident across all edge blocks (the classic
reduction-innermost schedule). Each pane is single-buffered; the two of
them bound the vertex count (``common.check_pane_rows``: 393,216 rows at
lane tile 128) — the vertex dimension beyond that is tiled by the
*distributed* vertex partition (core/distributed.py), not by this kernel.

The edge loop reads its per-edge scalars (src, dst, h, lo, thr) from one
int32 table blocked into SMEM, and reads/writes int8 rows through their
aligned (32, 128) tile (``common.read_row`` / ``update_row``).

Jacobi semantics: gathers read the input pane, maxes accumulate into the
output pane — bit-identical to kernels/ref.py for any edge order.

Diffusion-model hook: the per-edge hash ``h`` and interval offset ``lo``
arrive as operands (hash once per build instead of once per sweep), and the
activation decision is a static ``predicate`` callable — default
sampling.fused_predicate, pure VPU ops, legal inside the kernel body.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (EDGE_BLOCK, REG_TILE, VISITED,
                                  check_pane_rows, clamp_block,
                                  compiler_params, edge_block_for, edge_mask,
                                  edge_table, edge_table_spec, pad_amount,
                                  pad_rows, pallas_call, pane_spec, read_row,
                                  update_row)
from repro.kernels.ref import edge_args

#: rows of the SMEM edge table the sweep kernels read: src, dst, h, lo, thr
SRC, DST, HLT = 0, 1, (2, 3, 4)


def pad_register_axis(m, x, reg_tile: int):
    """Round the register axis up to a multiple of ``reg_tile``: padded x
    slots are 0 and the padded matrix columns VISITED (sticky under
    max-merge), so they never change and are sliced off by the caller."""
    pad = pad_amount(x.shape[0], reg_tile)
    if pad:
        x = jnp.pad(x, (0, pad))
        if m is not None:
            m = jnp.pad(m, ((0, 0), (0, pad)), constant_values=VISITED)
    return m, x


def _propagate_kernel(tab_ref, x_ref, m_ref, out_ref, *, edge_block: int,
                      predicate):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = m_ref[...]

    x = x_ref[...]

    def body(i, carry):
        mask = edge_mask(tab_ref, i, HLT, x, predicate)  # fused sampling
        pulled = read_row(m_ref, tab_ref[DST, i])  # Jacobi gather of v's tile
        contrib = jnp.where(mask, pulled, VISITED)
        # sticky visited: a VISITED register never resurrects
        update_row(out_ref, tab_ref[SRC, i],
                   lambda cur: jnp.where(cur == VISITED, cur,
                                         jnp.maximum(cur, contrib)))
        return carry

    jax.lax.fori_loop(0, edge_block, body, 0)


def edge_sweep_call(kernel, name: str, m, src, dst, thr, x, h, lo, *,
                    edge_block: int, reg_tile: int, predicate):
    """Launch one (register tile x edge block) sweep kernel over the packed
    edge table; shared by the SIMULATE and CASCADE sweeps."""
    n_pad, num_regs = m.shape
    reg_tile = clamp_block(num_regs, reg_tile)
    rows = check_pane_rows(name, n_pad, 2, reg_tile)
    eb = edge_block_for(src.shape[0], edge_block)
    tab = edge_table((src, dst, h, lo, thr), eb)
    m_in, x = pad_register_axis(m, x.astype(jnp.uint32), reg_tile)
    regs_pad = x.shape[0]
    out = pallas_call(
        partial(kernel, edge_block=eb, predicate=predicate),
        grid=(regs_pad // reg_tile, tab.shape[1] // eb),
        in_specs=[
            edge_table_spec(5, eb, lambda r, e: (0, e)),
            pl.BlockSpec((1, reg_tile), lambda r, e: (0, r)),
            pane_spec(rows, reg_tile, lambda r, e: (0, r)),
        ],
        out_specs=pane_spec(rows, reg_tile, lambda r, e: (0, r)),
        out_shape=jax.ShapeDtypeStruct((rows, regs_pad), jnp.int8),
        compiler_params=compiler_params("parallel", "arbitrary"),
    )(tab, x[None, :], pad_rows(m_in, rows))
    return out[:n_pad, :num_regs]


@partial(jax.jit, static_argnames=("seed", "edge_block", "reg_tile",
                                   "predicate"))
def propagate_sweep_pallas(m, src, dst, thr, x, h=None, lo=None, *, seed: int = 0,
                           edge_block: int = EDGE_BLOCK, reg_tile: int = REG_TILE,
                           predicate=None):
    h, lo, predicate = edge_args(src, dst, thr, h, lo, predicate, seed)
    return edge_sweep_call(_propagate_kernel, "propagate_sweep", m, src, dst,
                           thr, x, h, lo, edge_block=edge_block,
                           reg_tile=reg_tile, predicate=predicate)
