"""Pallas kernel: ``num_sweeps`` fused SIMULATE sweeps in one launch.

The single-sweep kernel (kernels/sketch_propagate.py) keeps its register
panes VMEM-resident across edge blocks, but the ``local_sweeps`` prologues
of the ring executors re-launch it per sweep — every extra comm-free sweep
round-trips the whole register matrix through HBM. This kernel runs the
sweep loop *inside* the launch: per register-lane tile, the current and
accumulator panes stay in VMEM for all ``num_sweeps`` iterations and HBM
sees the matrix exactly twice (load + final store).

Schedule: grid = (J / lane_tile, num_sweeps, E / edge_block). The edge table
streams through SMEM block by block, once per sweep (the fused trade:
re-reading the small edge list buys register-pane residency).
``lane_tile`` is the model-aware FASST lane-fill knob surfaced by
``repro.tune`` as ``KernelConfig.lane_fill``: per-register-column
independence of the Jacobi max-merge makes any tile width bit-identical, so
density is purely a performance choice (``lt``'s remixed vertex hash
changes which lanes are live per edge, shifting the optimum).

VMEM working set per lane tile: three single-buffered ``(n_pad, lane_tile)``
int8 panes — the input, the accumulator (the output block) and the previous
sweep's pane, a ``scratch_shapes`` buffer — which bounds the vertex count
at 262,144 rows for lane tile 128 (``common.check_pane_rows``).

Jacobi semantics: every sweep gathers from the previous sweep's pane only,
so results are bit-identical to ``num_sweeps`` applications of
kernels/ref.py's single sweep for any edge order and any lane tile.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (EDGE_BLOCK, REG_TILE, VISITED,
                                  check_pane_rows, clamp_block,
                                  compiler_params, edge_block_for, edge_mask,
                                  edge_table, edge_table_spec, pad_rows,
                                  pallas_call, pane_spec, read_row,
                                  update_row)
from repro.kernels.ref import edge_args
from repro.kernels.sketch_propagate import (DST, HLT, SRC,
                                            pad_register_axis)


def _fused_sweep_kernel(tab_ref, x_ref, m_ref, out_ref, prev_ref, *,
                        edge_block: int, predicate):
    sweep, eb = pl.program_id(1), pl.program_id(2)

    @pl.when(jnp.logical_and(sweep == 0, eb == 0))
    def _load():
        out_ref[...] = m_ref[...]

    @pl.when(eb == 0)
    def _next_sweep():
        prev_ref[...] = out_ref[...]   # this sweep gathers from the last one

    x = x_ref[...]

    def body(i, carry):
        mask = edge_mask(tab_ref, i, HLT, x, predicate)  # fused sampling
        pulled = read_row(prev_ref, tab_ref[DST, i])      # Jacobi gather
        contrib = jnp.where(mask, pulled, VISITED)
        # sticky visited: a VISITED register never resurrects
        update_row(out_ref, tab_ref[SRC, i],
                   lambda cur: jnp.where(cur == VISITED, cur,
                                         jnp.maximum(cur, contrib)))
        return carry

    jax.lax.fori_loop(0, edge_block, body, 0)


@partial(jax.jit, static_argnames=("seed", "num_sweeps", "lane_tile",
                                   "predicate"))
def fused_sweep_pallas(m, src, dst, thr, x, h=None, lo=None, *, seed: int = 0,
                       num_sweeps: int = 1, lane_tile: int = REG_TILE,
                       predicate=None):
    h, lo, predicate = edge_args(src, dst, thr, h, lo, predicate, seed)
    if num_sweeps <= 0:
        return m
    n_pad, num_regs = m.shape
    lane_tile = clamp_block(num_regs, lane_tile)
    rows = check_pane_rows("fused_sweep", n_pad, 3, lane_tile)
    eb = edge_block_for(src.shape[0], EDGE_BLOCK)
    tab = edge_table((src, dst, h, lo, thr), eb)
    m_in, x = pad_register_axis(m, x.astype(jnp.uint32), lane_tile)
    regs_pad = x.shape[0]
    out = pallas_call(
        partial(_fused_sweep_kernel, edge_block=eb, predicate=predicate),
        grid=(regs_pad // lane_tile, num_sweeps, tab.shape[1] // eb),
        in_specs=[
            edge_table_spec(5, eb, lambda r, s, e: (0, e)),
            pl.BlockSpec((1, lane_tile), lambda r, s, e: (0, r)),
            pane_spec(rows, lane_tile, lambda r, s, e: (0, r)),
        ],
        out_specs=pane_spec(rows, lane_tile, lambda r, s, e: (0, r)),
        out_shape=jax.ShapeDtypeStruct((rows, regs_pad), jnp.int8),
        scratch_shapes=[pltpu.VMEM((rows, lane_tile), jnp.int8)],
        compiler_params=compiler_params("parallel", "arbitrary", "arbitrary"),
    )(tab, x[None, :], pad_rows(m_in, rows))
    return out[:n_pad, :num_regs]
