"""Shared helpers for the Pallas kernel bodies.

Everything here must be expressible inside a Pallas TPU kernel: uint32
vector arithmetic, shift-based clz (TPU Mosaic has no clz primitive we rely
on), and the murmur-style mixers duplicated from repro.core.sampling so the
kernel bodies have no external dependencies.

It also holds what every kernel of this package shares about the chip:

  * :func:`pallas_call` — interpret exactly when the computation's platform
    is CPU, compile with Mosaic everywhere else (no global switch);
  * the int8 row access of the edge-loop kernels (:func:`read_row` /
    :func:`update_row`) — Mosaic loads int8 rows only at offsets aligned to
    the (32, 128) native tile, so a row is read out of its aligned tile;
  * the VMEM budget of the resident register panes, and the largest vertex
    count each edge kernel accepts (:func:`check_pane_rows`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_M1 = 0x85EBCA6B
_M2 = 0xC2B2AE35
_GOLD = 0x9E3779B9

# Default TPU tiling. Registers ride the lane dimension (128 lanes per
# vreg); edge blocks are sized so (edge_block x reg_tile) uint32 scratch
# stays well under VMEM.
REG_TILE = 128
EDGE_BLOCK = 512
VERTEX_BLOCK = 256

#: lane width of a vreg; blocked edge axes are rounded up to it
LANES = 128
#: rows of one native int8 VMEM tile (one (32, 128) int8 vreg)
ROW_TILE = 32
#: scoped VMEM the edge kernels ask Mosaic for (a v5e core has 128 MiB)
VMEM_LIMIT_BYTES = 112 * 2**20
#: the share of it the resident int8 register panes may take
PANE_VMEM_BYTES = 96 * 2**20

VISITED = -1  # python literal: weak-typed inside kernels (no captured consts)


def clamp_block(n: int, block: int) -> int:
    """Block size actually used for an ``n``-long axis: at least 1, at most
    ``n`` (a block larger than the axis is one full-axis block). Kernels
    clamp and pad the axis up to a block multiple rather than search for a
    divisor, which degrades to block = 1 on a prime axis."""
    return max(1, min(int(block), int(n)))


def pad_amount(n: int, block: int) -> int:
    """Trailing padding that rounds ``n`` up to a multiple of ``block``.

    Edge operands padded this way use width-0 filler (``thr = 0``): the
    universal interval predicate ``((X ^ h) - lo) mod 2^32 < thr`` can never
    fire with ``thr == 0``, so a padded edge contributes the max-merge
    identity (VISITED) to propagate sweeps and never marks anything in
    cascade sweeps — results are bit-identical to the unpadded axis.
    """
    return (-int(n)) % int(block)


def round_up(n: int, block: int) -> int:
    return int(n) + pad_amount(n, block)


def edge_block_for(num_edges: int, edge_block: int) -> int:
    """Edge block of the SMEM edge table: the requested block clamped to the
    edge count, rounded up to whole lanes (Mosaic's block rule)."""
    return round_up(clamp_block(num_edges, edge_block), LANES)


def edge_table(cols, edge_block: int) -> jnp.ndarray:
    """Stack per-edge operands into one int32 ``(len(cols), E_pad)`` table
    (uint32 columns bit-cast) whose edge axis is padded to ``edge_block``
    with zeros: the kernels read it from SMEM one scalar at a time, and a
    zero threshold makes every padded edge predicate-dead."""
    rows = [c if c.dtype == jnp.int32 else
            jax.lax.bitcast_convert_type(c.astype(jnp.uint32), jnp.int32)
            for c in cols]
    tab = jnp.stack(rows)
    pad = pad_amount(tab.shape[1], edge_block)
    return jnp.pad(tab, ((0, 0), (0, pad))) if pad else tab


def edge_table_spec(num_cols: int, edge_block: int, index_map):
    return pl.BlockSpec((num_cols, edge_block), index_map,
                        memory_space=pltpu.SMEM)


def pane_spec(rows: int, lane_tile: int, index_map):
    """A resident ``(rows, lane_tile)`` register pane: its block index only
    moves with the lane tile, so one buffer suffices (double-buffering it
    would halve the vertex count that fits VMEM)."""
    return pl.BlockSpec((rows, lane_tile), index_map,
                        pipeline_mode=pl.Buffered(1))


def compiler_params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def max_pane_rows(num_panes: int, lane_tile: int) -> int:
    """Largest row count whose ``num_panes`` resident int8 panes of
    ``lane_tile`` lanes fit :data:`PANE_VMEM_BYTES`."""
    rows = PANE_VMEM_BYTES // (num_panes * round_up(lane_tile, LANES))
    return rows - rows % ROW_TILE


def check_pane_rows(kernel: str, n_rows: int, num_panes: int,
                    lane_tile: int) -> int:
    """Rows of the kernel's panes (``n_rows`` rounded up to the int8 tile);
    raises with the reason when they cannot all be resident in VMEM."""
    rows = round_up(n_rows, ROW_TILE)
    limit = max_pane_rows(num_panes, lane_tile)
    if rows > limit:
        raise ValueError(
            f"{kernel}: {num_panes} resident int8 register panes of "
            f"{rows} rows x {lane_tile} lanes need "
            f"{num_panes * rows * round_up(lane_tile, LANES) / 2**20:.0f} MiB "
            f"of VMEM, over the {PANE_VMEM_BYTES // 2**20} MiB the panes may "
            f"take; this kernel accepts at most {limit} vertex rows at lane "
            f"tile {lane_tile} (shard the vertex axis over a mesh, or use "
            f"impl='ref')")
    return rows


def pad_rows(m: jnp.ndarray, rows: int, cols: int = 0) -> jnp.ndarray:
    """Pad a register matrix with VISITED up to ``rows`` (and ``cols``):
    no edge reads or writes a padded row, and a VISITED register is sticky,
    so the padding never changes and the caller slices it off."""
    pads = ((0, rows - m.shape[0]), (0, max(cols - m.shape[1], 0)))
    if pads == ((0, 0), (0, 0)):
        return m
    return jnp.pad(m, pads, constant_values=VISITED)


def pallas_call(kernel, **kwargs):
    """``pl.pallas_call`` that runs the Pallas interpreter when the
    computation is lowered for CPU and Mosaic-compiles it for any other
    platform. The choice is made per lowering (``lax.platform_dependent``),
    so one traced program interprets on a CPU device and compiles on the
    chip; a kernel Mosaic refuses raises, it never falls back."""
    def call(*args):
        return jax.lax.platform_dependent(
            *args,
            cpu=lambda *a: pl.pallas_call(kernel, interpret=True, **kwargs)(*a),
            default=lambda *a: pl.pallas_call(kernel, **kwargs)(*a))
    return call


def edge_mask(tab_ref, i, cols, x, predicate):
    """``predicate`` of edge ``i`` against the lane tile's uint32 ``x`` —
    the (h, lo, thr) operands sit in rows ``cols`` of the SMEM edge table
    and are broadcast to ``x``'s shape before the bit-cast to uint32."""
    h, lo, thr = (jax.lax.bitcast_convert_type(
        jnp.full(x.shape, tab_ref[c, i], jnp.int32), jnp.uint32) for c in cols)
    return predicate(h, lo, thr, x)


def _row_tile(ref, row):
    """The aligned tile holding ``row``: (base, int32 tile, row mask, row)."""
    base = pl.multiple_of((row // ROW_TILE) * ROW_TILE, ROW_TILE)
    tile = ref[pl.ds(base, ROW_TILE), :].astype(jnp.int32)
    hit = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0) == row - base
    value = jnp.max(jnp.where(hit, tile, -128), axis=0, keepdims=True)
    return base, tile, hit, value


def read_row(ref, row) -> jnp.ndarray:
    """Row ``row`` of an int8 pane as a ``(1, lanes)`` int32 vector."""
    return _row_tile(ref, row)[3]


def update_row(ref, row, fn) -> None:
    """``ref[row] = fn(ref[row])`` on an int8 pane, through the aligned tile
    that holds the row (``fn`` maps a ``(1, lanes)`` int32 row)."""
    base, tile, hit, cur = _row_tile(ref, row)
    new = jnp.where(hit, fn(cur), tile)
    ref[pl.ds(base, ROW_TILE), :] = new.astype(ref.dtype)


def kmix32(x: jnp.ndarray) -> jnp.ndarray:
    """murmur3 fmix32 (kernel-local copy of sampling.mix32)."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(_M1)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(_M2)
    x = x ^ (x >> 16)
    return x


def kregister_hash(vertex: jnp.ndarray, reg: jnp.ndarray, seed: int) -> jnp.ndarray:
    u = vertex.astype(jnp.uint32)
    j = reg.astype(jnp.uint32)
    return kmix32(kmix32(u * jnp.uint32(_GOLD) + jnp.uint32(seed ^ 0x5BD1E995)) ^ (j * jnp.uint32(_M2)))


def kclz32(x: jnp.ndarray) -> jnp.ndarray:
    """clz via 5-step binary search — pure shifts/compares (VPU friendly)."""
    x = x.astype(jnp.uint32)
    n = jnp.full(x.shape, 32, dtype=jnp.int32)
    for shift in (16, 8, 4, 2, 1):
        big = x >= (jnp.uint32(1) << jnp.uint32(shift))
        n = jnp.where(big, n - shift, n)
        x = jnp.where(big, x >> jnp.uint32(shift), x)
    return n - x.astype(jnp.int32)
