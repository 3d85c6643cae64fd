"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantic ground truth: the Pallas bodies (interpreted on
CPU, Mosaic-compiled on a TPU) must match them exactly — all kernels
here are integer/bit-exact except the cardinality statistics (float32,
compared with allclose).

Sweep semantics are Jacobi: every sweep gathers from the *input* register
matrix and scatter-reduces into a fresh accumulator. This makes the result
independent of edge order, so ref, Pallas, and all distributed schedules
agree bit-for-bit at every sweep (not only at the fixpoint).

Diffusion-model hook: every sweep takes optional per-edge ``h`` (precomputed
sample-independent hash) and ``lo`` (interval low endpoint) operands plus a
static ``predicate`` callable (default: sampling.fused_predicate, the
universal interval form). When ``h``/``lo`` are omitted the legacy
weighted-cascade behaviour is reproduced bit-for-bit: h = edge_hash(src,
dst, seed), lo = 0, and the predicate collapses to ``(X ^ h) < thr``.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.sampling import edge_hash, fused_predicate
from repro.core.sketch import C_HARMONIC, VISITED
from repro.kernels.common import clamp_block, pad_amount, round_up


def _chunked(src, dst, h, lo, thr, edge_chunk: int):
    """Reshape edge operands to (n_chunks, edge_chunk), padding the tail
    chunk with predicate-dead edges (thr=0 never fires — see
    ``common.pad_amount``) so any chunk size is legal, not just divisors.
    Returns (xs, edge_chunk_used)."""
    num_edges = src.shape[0]
    edge_chunk = clamp_block(num_edges, edge_chunk)
    pad = pad_amount(num_edges, edge_chunk)
    ops = (src, dst, h, lo, thr)
    if pad:
        ops = tuple(jnp.pad(a, (0, pad)) for a in ops)
    n_chunks = (num_edges + pad) // edge_chunk
    return tuple(a.reshape(n_chunks, edge_chunk) for a in ops), edge_chunk


def edge_args(src, dst, thr, h, lo, predicate, seed):
    """Canonicalize the model hook (shared by the ref and Pallas sweeps):
    default hash/offset/predicate give the legacy threshold compare."""
    if h is None:
        h = edge_hash(src, dst, seed=seed)
    if lo is None:
        lo = jnp.zeros(thr.shape, jnp.uint32)
    if predicate is None:
        predicate = fused_predicate
    return h, lo, predicate


def _edge_mask(h, lo, thr, x, predicate):
    """(E,) per-edge operands × (R,) X -> (E, R) bool live mask."""
    return predicate(h[:, None].astype(jnp.uint32), lo[:, None].astype(jnp.uint32),
                     thr[:, None].astype(jnp.uint32), x[None, :].astype(jnp.uint32))


def fused_sample_ref(src: jnp.ndarray, dst: jnp.ndarray, thr: jnp.ndarray,
                     x: jnp.ndarray, h=None, lo=None, *, seed: int = 0,
                     predicate=None) -> jnp.ndarray:
    """(E,) edges × (R,) X -> (E, R) uint8 membership mask (paper eq. (2))."""
    h, lo, predicate = edge_args(src, dst, thr, h, lo, predicate, seed)
    return _edge_mask(h, lo, thr, x, predicate).astype(jnp.uint8)


def sketch_fill_ref(m: jnp.ndarray, *, reg_offset: int = 0, seed: int = 0) -> jnp.ndarray:
    """FILL-SKETCHES (paper Alg. 1) with the visited early-exit.

    m: int8[n_pad, J] current registers; VISITED entries are preserved,
    everything else is re-initialized to clz(h_j(u)).
    """
    from repro.core.sampling import register_hash

    n_pad, num_regs = m.shape
    u = jnp.arange(n_pad, dtype=jnp.uint32)[:, None]
    j = jnp.arange(num_regs, dtype=jnp.uint32)[None, :] + jnp.uint32(reg_offset)
    fresh = jax.lax.clz(register_hash(u, j, seed=seed)).astype(jnp.int8)
    return jnp.where(m == VISITED, m, fresh)


@partial(jax.jit, static_argnames=("edge_chunk", "seed", "predicate"))
def propagate_sweep_ref(m: jnp.ndarray, src: jnp.ndarray, dst: jnp.ndarray,
                        thr: jnp.ndarray, x: jnp.ndarray, h=None, lo=None, *,
                        edge_chunk: int = 2048, seed: int = 0,
                        predicate=None) -> jnp.ndarray:
    """One SIMULATE sweep (paper Alg. 2): pull-based sketch max-merge.

    For every edge (u, v) live in sim j, M[u, j] <- max(M[u, j], M[v, j]).
    Visited registers are sticky. Jacobi: gathers read the input ``m``.
    """
    h, lo, predicate = edge_args(src, dst, thr, h, lo, predicate, seed)
    xs, _ = _chunked(src, dst, h, lo, thr, edge_chunk)

    def body(acc, chunk):
        s, d, hh, ll, t = chunk
        mask = _edge_mask(hh, ll, t, x, predicate)
        vals = m[d]  # (chunk, J) — pull from out-neighbors (Jacobi: reads input m)
        contrib = jnp.where(mask, vals, jnp.int8(VISITED))
        acc = acc.at[s].max(contrib)
        return acc, None

    acc, _ = jax.lax.scan(body, m, xs)
    return jnp.where(m == VISITED, m, acc)


@partial(jax.jit, static_argnames=("num_sweeps", "edge_chunk", "lane_fill",
                                   "seed", "predicate"))
def fused_sweep_ref(m: jnp.ndarray, src: jnp.ndarray, dst: jnp.ndarray,
                    thr: jnp.ndarray, x: jnp.ndarray, h=None, lo=None, *,
                    num_sweeps: int = 1, edge_chunk: int = 2048,
                    lane_fill: int = 0, seed: int = 0,
                    predicate=None) -> jnp.ndarray:
    """``num_sweeps`` SIMULATE sweeps fused into one traced program.

    Each sweep is exactly :func:`propagate_sweep_ref`; fusing them means one
    dispatch (and, on device, one HBM round-trip of the register matrix)
    instead of one per sweep. ``lane_fill`` processes the register axis in
    that many columns at a time (0 = full width): every column of the Jacobi
    max-merge is independent of every other, so slabbing is bit-identical —
    it only shrinks the per-chunk mask/gather working set from
    ``edge_chunk x num_regs`` to ``edge_chunk x lane_fill``, which is what
    keeps high-register-count sweeps cache-resident.
    """
    h, lo, predicate = edge_args(src, dst, thr, h, lo, predicate, seed)
    xs, _ = _chunked(src, dst, h, lo, thr, edge_chunk)
    num_regs = int(m.shape[1])
    fill = int(lane_fill) if 0 < int(lane_fill) < num_regs else num_regs

    def one_sweep(m_in):
        def slab(j0, j1):
            x_s, m_s = x[j0:j1], m_in[:, j0:j1]

            def body(acc, chunk):
                s, d, hh, ll, t = chunk
                mask = _edge_mask(hh, ll, t, x_s, predicate)
                contrib = jnp.where(mask, m_s[d], jnp.int8(VISITED))
                return acc.at[s].max(contrib), None

            acc, _ = jax.lax.scan(body, m_s, xs)
            return jnp.where(m_s == VISITED, m_s, acc)

        if fill >= num_regs:
            return slab(0, num_regs)
        return jnp.concatenate(
            [slab(j0, min(j0 + fill, num_regs))
             for j0 in range(0, num_regs, fill)], axis=1)

    out = m
    for _ in range(int(num_sweeps)):
        out = one_sweep(out)
    return out


#: slots per piece of the cascade's pull layout (chosen on a v5e, PERF.md)
CASCADE_SLOT_WIDTH = 8


class CascadeLayout(NamedTuple):
    """The cascade sweep's pull layout: every vertex's in-arcs cut into
    pieces of ``CASCADE_SLOT_WIDTH`` slots, pieces in destination order, the
    tail piece of each vertex padded with dead slots (``thr = 0``, which
    never fires). Every vertex owns at least one piece, so the pieces of one
    chunk belong to at most as many consecutive vertices as the chunk has
    pieces. The arrays are sized by the bound ``ceil(m / W) + n_pad``
    pieces, whatever the degrees: graphs of equal (n_pad, m) share one
    program. Depends only on the arcs."""

    src: jnp.ndarray             # int32[chunks, C] slot's arc source
    h: jnp.ndarray               # uint32[chunks, C]
    lo: jnp.ndarray              # uint32[chunks, C]
    thr: jnp.ndarray             # uint32[chunks, C] 0 on dead slots
    owner: jnp.ndarray           # int32[chunks, C // W] piece's destination
    chunks: jnp.ndarray          # int32[] leading chunks that hold pieces
    pad_share: jnp.ndarray       # float32[] dead slots / slots of the pieces
    split_vertices: jnp.ndarray  # int32[] vertices with more than W arcs


@partial(jax.jit, static_argnames=("n_pad", "chunk"))
def cascade_layout(src, dst, thr, h, lo, *, n_pad: int,
                   chunk: int) -> CascadeLayout:
    """Build the :class:`CascadeLayout` of the arcs on the device. The arcs
    may come in any order. ``chunk`` is the sweep's scan chunk in slots,
    rounded up to whole pieces."""
    w = CASCADE_SLOT_WIDTH
    num_edges = src.shape[0]
    bound = -(-num_edges // w) + n_pad
    chunk = round_up(clamp_block(bound * w, chunk), w)
    per_chunk = chunk // w
    num_chunks = -(-bound // per_chunk)

    dst = dst.astype(jnp.int32)
    order = jnp.argsort(dst)
    dst = dst[order]
    start = jnp.searchsorted(dst, jnp.arange(n_pad + 1, dtype=jnp.int32),
                             method="sort")
    deg = jnp.diff(start)
    pieces = jnp.maximum(1, -(-deg // w))
    poff = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(pieces)])
    total = poff[n_pad]
    first = poff[:n_pad] * w      # vertex v owns slots [first[v], first[v+1])

    def per_owner(marks, size, vals):
        # vals[v] on every slot or piece v owns: every vertex owns at least
        # one, so the marks are distinct and a running sum of steps is exact
        steps = jnp.diff(vals, prepend=jnp.zeros(1, vals.dtype))
        return jnp.cumsum(jnp.zeros(size, vals.dtype).at[marks].add(steps))

    # everything stays one-dimensional: a (pieces, W) array would be padded
    # to whole lane tiles on a TPU
    vertices = jnp.arange(n_pad, dtype=jnp.int32)
    owner = per_owner(poff[:n_pad], num_chunks * per_chunk, vertices)
    arc = (jnp.arange(num_chunks * chunk, dtype=jnp.int32)
           + per_owner(first, num_chunks * chunk, start[:n_pad] - first))
    # slots past a vertex's last arc (and past the last piece) are dead
    valid = arc < per_owner(first, num_chunks * chunk, start[1:])
    arc = order[jnp.minimum(arc, num_edges - 1)]

    def slots(a):
        return jnp.where(valid, a[arc], 0).astype(a.dtype).reshape(
            num_chunks, chunk)

    return CascadeLayout(
        src=slots(src), h=slots(h), lo=slots(lo), thr=slots(thr),
        owner=owner.reshape(num_chunks, per_chunk),
        chunks=-(-total // per_chunk),
        pad_share=1.0 - jnp.float32(num_edges) / (total * w).astype(jnp.float32),
        split_vertices=jnp.sum(deg > w, dtype=jnp.int32))


@partial(jax.jit, static_argnames=("predicate",))
def cascade_sweep_ref(m: jnp.ndarray, layout: CascadeLayout, x: jnp.ndarray,
                      *, predicate=None) -> jnp.ndarray:
    """One CASCADE sweep (paper Alg. 3): propagate visitedness forward.

    For every edge (u, v) live in sim j with M[u, j] == VISITED, mark
    M[v, j] <- VISITED. Jacobi semantics as above.

    A pull over ``layout``, the sweep's only edge input: each chunk gathers
    its slots' source rows and ORs them into the rows of the consecutive
    vertices that own them, through one product of a (vertices x slots)
    one-hot with the chunk's live mask, and one contiguous read-modify-write
    of those rows. No per-arc scatter.
    """
    predicate = predicate or fused_predicate
    n_pad, _ = m.shape
    per_chunk = layout.owner.shape[1]
    w = layout.src.shape[1] // per_chunk
    rows = min(per_chunk, n_pad)
    vis = m == VISITED

    def body(k, acc):
        mask = _edge_mask(layout.h[k], layout.lo[k], layout.thr[k], x, predicate)
        live = jnp.logical_and(mask, vis[layout.src[k]])
        owner = layout.owner[k]
        v0 = jnp.minimum(owner[0], n_pad - rows)
        onehot = (jnp.repeat(owner - v0, w)[None, :]
                  == jnp.arange(rows, dtype=jnp.int32)[:, None])
        # counts of live slots, exact in float32: > 0 is their OR
        pulled = jnp.dot(onehot.astype(jnp.bfloat16), live.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32) > 0
        cur = jax.lax.dynamic_slice_in_dim(acc, v0, rows)
        return jax.lax.dynamic_update_slice_in_dim(acc, cur | pulled, v0, 0)

    acc = jax.lax.fori_loop(0, layout.chunks, body, vis)
    return jnp.where(acc, jnp.int8(VISITED), m)


def cardinality_stats_ref(m: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-vertex sufficient statistics for the HLL estimator.

    Returns (sum_{valid} 2^-M[u, j], count_valid) as float32[n_pad] each.
    """
    valid = m != VISITED
    stat = jnp.sum(jnp.where(valid, jnp.exp2(-m.astype(jnp.float32)), 0.0), axis=-1)
    count = jnp.sum(valid, axis=-1).astype(jnp.float32)
    return stat, count


def estimate_ref(m: jnp.ndarray) -> jnp.ndarray:
    """End-to-end HLL estimate (stats + finish) — matches sketch.estimate_cardinality."""
    num_regs = m.shape[-1]
    stat, count = cardinality_stats_ref(m)
    est = jnp.float32(C_HARMONIC) * count / jnp.maximum(stat, 1e-30)
    return jnp.where(count > 0, est * (count / num_regs), 0.0)
