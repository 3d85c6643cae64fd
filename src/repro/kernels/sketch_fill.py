"""Pallas kernel: FILL-SKETCHES (paper Alg. 1).

M[u, j] <- clz(h_j(u)) for non-visited registers; VISITED (-1) entries are
preserved (the Alg. 1 line-5 early exit, which on TPU is a lane select
rather than a thread `continue`).

TPU tiling: grid = (n_pad / VERTEX_BLOCK, J / REG_TILE); each step writes a
(256, 128) int8 tile (32 KiB). The vertex/register ids are derived from the
grid position with iota — the only input is the previous register tile (for
the visited mask), so the kernel is write-bandwidth-bound as in the paper.
Both axes are padded up to whole blocks (VISITED filler, sliced off), so
any ``n_pad`` keeps the int8 (32, 128) block rule.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (REG_TILE, ROW_TILE, VERTEX_BLOCK, VISITED,
                                  clamp_block, kclz32, kregister_hash,
                                  pad_rows, pallas_call, round_up)


def _sketch_fill_kernel(m_ref, out_ref, *, vertex_block: int, reg_tile: int,
                        reg_offset: int, seed: int):
    vb = pl.program_id(0)
    rb = pl.program_id(1)
    u0 = vb * vertex_block
    j0 = rb * reg_tile + reg_offset
    u = (jax.lax.broadcasted_iota(jnp.int32, (vertex_block, reg_tile), 0) + u0).astype(jnp.uint32)
    j = (jax.lax.broadcasted_iota(jnp.int32, (vertex_block, reg_tile), 1) + j0).astype(jnp.uint32)
    fresh = kclz32(kregister_hash(u, j, seed))
    prev = m_ref[...].astype(jnp.int32)
    out_ref[...] = jnp.where(prev == VISITED, prev, fresh).astype(jnp.int8)


@partial(jax.jit, static_argnames=("reg_offset", "seed", "vertex_block", "reg_tile"))
def sketch_fill_pallas(m, *, reg_offset: int = 0, seed: int = 0,
                       vertex_block: int = VERTEX_BLOCK, reg_tile: int = REG_TILE):
    n_pad, num_regs = m.shape
    vertex_block = round_up(clamp_block(n_pad, vertex_block), ROW_TILE)
    reg_tile = clamp_block(num_regs, reg_tile)
    m = pad_rows(m, round_up(n_pad, vertex_block), round_up(num_regs, reg_tile))
    rows, regs_pad = m.shape
    out = pallas_call(
        partial(_sketch_fill_kernel, vertex_block=vertex_block, reg_tile=reg_tile,
                reg_offset=reg_offset, seed=seed),
        grid=(rows // vertex_block, regs_pad // reg_tile),
        in_specs=[pl.BlockSpec((vertex_block, reg_tile), lambda v, r: (v, r))],
        out_specs=pl.BlockSpec((vertex_block, reg_tile), lambda v, r: (v, r)),
        out_shape=jax.ShapeDtypeStruct((rows, regs_pad), jnp.int8),
    )(m)
    return out[:n_pad, :num_regs]
