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

import jax
import jax.numpy as jnp

from repro.core.sampling import edge_hash, fused_predicate
from repro.core.sketch import C_HARMONIC, VISITED
from repro.kernels.common import clamp_block, pad_amount


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


@partial(jax.jit, static_argnames=("edge_chunk", "seed", "predicate"))
def cascade_sweep_ref(m: jnp.ndarray, src: jnp.ndarray, dst: jnp.ndarray,
                      thr: jnp.ndarray, x: jnp.ndarray, h=None, lo=None, *,
                      edge_chunk: int = 2048, seed: int = 0,
                      predicate=None) -> jnp.ndarray:
    """One CASCADE sweep (paper Alg. 3): propagate visitedness forward.

    For every edge (u, v) live in sim j with M[u, j] == VISITED, mark
    M[v, j] <- VISITED. Jacobi semantics as above.
    """
    h, lo, predicate = edge_args(src, dst, thr, h, lo, predicate, seed)
    xs, _ = _chunked(src, dst, h, lo, thr, edge_chunk)
    vis = m == VISITED

    def body(acc, chunk):
        s, d, hh, ll, t = chunk
        mask = _edge_mask(hh, ll, t, x, predicate)
        newly = jnp.logical_and(mask, vis[s]).astype(jnp.uint8)
        acc = acc.at[d].max(newly)
        return acc, None

    acc, _ = jax.lax.scan(body, vis.astype(jnp.uint8), xs)
    return jnp.where(acc.astype(bool), jnp.int8(VISITED), m)


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
