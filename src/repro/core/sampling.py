"""Hash-based fused sampling (paper §2.2).

An edge (u, v) belongs to sample r iff

    (X_r XOR h(u, v)) < w_uv * 2^32        (uint32 arithmetic)

so sampling costs one XOR + one compare per (edge, sample) — no stored
samples, no RNG state. ``X`` is a host-generated vector of R uniform uint32
values; ``h`` is a murmur3-style finalizer over the endpoint pair.

Everything here is dtype-pinned to uint32 and works identically in numpy
(host-side FASST partitioning) and jax.numpy (device kernels/refs).
"""
from __future__ import annotations

from typing import Union

import jax.numpy as jnp
import numpy as np

Array = Union[np.ndarray, jnp.ndarray]

# murmur3 / splitmix constants
_M1 = 0x85EBCA6B
_M2 = 0xC2B2AE35
_GOLD = 0x9E3779B9

UINT32_MAX = np.uint64(0xFFFFFFFF)


def _xp(x):
    return np if isinstance(x, np.ndarray) else jnp


def mix32(x: Array) -> Array:
    """Murmur3 fmix32 finalizer — full avalanche on uint32."""
    xp = _xp(x)
    x = x.astype(xp.uint32)
    x = x ^ (x >> 16)
    x = x * xp.uint32(_M1)
    x = x ^ (x >> 13)
    x = x * xp.uint32(_M2)
    x = x ^ (x >> 16)
    return x


def edge_hash(src: Array, dst: Array, seed: int = 0) -> Array:
    """h(u, v): order-sensitive 32-bit hash of an edge (paper eq. (1))."""
    xp = _xp(src)
    u = src.astype(xp.uint32)
    v = dst.astype(xp.uint32)
    h = mix32(u * xp.uint32(_GOLD) + xp.uint32(seed))
    return mix32(h ^ (v * xp.uint32(_M1) + xp.uint32(0x27D4EB2F)))


def register_hash(vertex: Array, reg: Array, seed: int = 0) -> Array:
    """h_j(u): per-register item hash used by the FM sketches (paper eq. (3))."""
    xp = _xp(vertex)
    u = vertex.astype(xp.uint32)
    j = reg.astype(xp.uint32)
    return mix32(mix32(u * xp.uint32(_GOLD) + xp.uint32(seed ^ 0x5BD1E995)) ^ (j * xp.uint32(_M2)))


def vertex_hash(vertex: Array, seed: int = 0) -> Array:
    """h(v): 32-bit hash of a single vertex — the per-destination hash the LT
    live-edge sampler uses (every in-edge of v shares it, so one uniform draw
    decides which in-edge, if any, is live)."""
    xp = _xp(vertex)
    v = vertex.astype(xp.uint32)
    return mix32(mix32(v * xp.uint32(_GOLD) + xp.uint32(seed ^ 0x165667B1)) ^ xp.uint32(0x27D4EB2F))


def fused_predicate(h: Array, lo: Array, width: Array, x: Array) -> Array:
    """The universal hash-fused edge-activation predicate of the model zoo:

        live(e, r)  <=>  ((X_r ^ h_e) - lo_e) mod 2^32  <  width_e

    one XOR + one subtract + one unsigned compare per (edge, sample), for
    every registered diffusion model:

      * threshold models (ic / wc / dic): lo = 0, width = w_eff * 2^32 —
        bit-identical to the paper's ``(X ^ h) < w * 2^32`` (§2.2, eq. (2));
      * interval models (lt) use the same operand layout but sample through
        ``remix_interval_predicate`` below — the raw XOR form here leaves
        cross-vertex selections too correlated for sound joint reachability.

    All operands must be uint32 (wraparound subtraction is the point);
    works identically for numpy and jnp, scalar or broadcast shapes, and is
    Pallas-kernel-safe (pure VPU ops).
    """
    return ((h ^ x) - lo) < width


def remix_interval_predicate(h: Array, lo: Array, width: Array, x: Array) -> Array:
    """Interval predicate with an avalanche remix of the per-(vertex, sample)
    uniform:  live  <=>  (mix32(X_r ^ h_v) - lo_e) mod 2^32 < width_e.

    The LT live-edge sampler needs joint path probabilities, not just
    marginals: the raw XOR ``X_r ^ h_v`` leaves interval membership across
    *different* vertices of one sample too correlated (the XOR of two such
    uniforms is the constant h_u ^ h_v), which measurably suppresses
    reachability. One extra fmix32 (shifts + multiplies, VPU-friendly,
    Pallas-safe) decorrelates vertices while keeping exclusivity: all
    in-edges of v still share one uniform per sample, so at most one fires.
    """
    return (mix32(h ^ x) - lo) < width


def weight_to_threshold(w: np.ndarray) -> np.ndarray:
    """Map probability w in [0,1] to a uint32 compare threshold w * 2^32."""
    thr = np.minimum(np.round(np.asarray(w, np.float64) * 4294967296.0), np.float64(UINT32_MAX))
    return thr.astype(np.uint32)


def make_x_vector(num_samples: int, seed: int = 0) -> np.ndarray:
    """The random vector X = {X_1..X_R} (host-side, uint32)."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, 1 << 32, size=num_samples, dtype=np.uint64).astype(np.uint32)


def sample_mask(edge_h: Array, thr: Array, x: Array) -> Array:
    """(E,) edge hashes × (R,) X values -> (E, R) bool sample membership.

    mask[e, r] = (X_r ^ h_e) < thr_e
    """
    xp = _xp(edge_h)
    return (edge_h[:, None] ^ x[None, :]) < thr.astype(xp.uint32)[:, None]


def clz32(x: Array) -> Array:
    """Count leading zeros of uint32 (vectorized, numpy path).

    jnp path should prefer jax.lax.clz; this exists for host-side numpy use
    and as a reference for the Pallas kernel body.
    """
    xp = _xp(x)
    x = x.astype(xp.uint32)
    n = xp.full(x.shape, 32, dtype=xp.int32)
    for shift in (16, 8, 4, 2, 1):
        big = x >= (xp.uint32(1) << xp.uint32(shift))
        n = xp.where(big, n - shift, n)
        x = xp.where(big, x >> xp.uint32(shift), x)
    return n - x.astype(xp.int32)  # x is now 0 or 1; subtract the found bit
