"""Pallas kernel: one CASCADE sweep (paper Alg. 3).

Propagates visitedness forward: for every edge (u, v) live in sim j with
``M[u, j] == VISITED``, mark ``M[v, j] <- VISITED``.

The paper's unified frontier queue + warp-ballot dedup is a GPU-occupancy
device with no TPU analogue (DESIGN.md §2); here the frontier is implicit —
a dense sweep over the (dst-sorted) edge list whose per-lane work is a
compare + select. The fixpoint driver (core/cascade.py) supplies the early
exit the queue provided: it stops as soon as a sweep changes nothing.

Same schedule, SMEM edge table, aligned int8 row access and VMEM bound as
sketch_propagate (register tile major, edge blocks minor, register panes
VMEM-resident); Jacobi semantics, bit-exact vs ref.py. Same diffusion-model
hook as sketch_propagate: per-edge (h, lo) operands plus a static
``predicate`` (default: the universal interval form).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (EDGE_BLOCK, REG_TILE, VISITED, edge_mask,
                                  read_row, update_row)
from repro.kernels.ref import edge_args
from repro.kernels.sketch_propagate import DST, HLT, SRC, edge_sweep_call


def _cascade_kernel(tab_ref, x_ref, m_ref, out_ref, *, edge_block: int,
                    predicate):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = m_ref[...]

    x = x_ref[...]

    def body(i, carry):
        mask = edge_mask(tab_ref, i, HLT, x, predicate)
        vis_u = read_row(m_ref, tab_ref[SRC, i]) == VISITED  # Jacobi read
        newly = jnp.logical_and(mask, vis_u)
        update_row(out_ref, tab_ref[DST, i],
                   lambda cur: jnp.where(newly, VISITED, cur))
        return carry

    jax.lax.fori_loop(0, edge_block, body, 0)


@partial(jax.jit, static_argnames=("seed", "edge_block", "reg_tile",
                                   "predicate"))
def cascade_sweep_pallas(m, src, dst, thr, x, h=None, lo=None, *, seed: int = 0,
                         edge_block: int = EDGE_BLOCK, reg_tile: int = REG_TILE,
                         predicate=None):
    h, lo, predicate = edge_args(src, dst, thr, h, lo, predicate, seed)
    return edge_sweep_call(_cascade_kernel, "cascade_sweep", m, src, dst, thr,
                           x, h, lo, edge_block=edge_block, reg_tile=reg_tile,
                           predicate=predicate)
