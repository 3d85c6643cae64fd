"""Plain reference of DiFuseR's seed selection under threshold sampling.

Written from the semantics the program states (paper Alg. 1-4, section 2.2),
in plain ``jax.numpy``; nothing of the program is imported.

- An edge (u, v) of weight w is live in simulation j iff
  ``(x_j XOR h(u, v)) < round(w * 2**32)``, with ``h`` a murmur3-finalised
  pair hash and ``x`` sorted ascending (FASST order).
- Register ``M[u, j]`` starts at ``clz(h_j(u))`` and is max-merged with the
  registers of u's live out-neighbours, one Jacobi sweep at a time, until a
  sweep changes nothing. ``-1`` marks a register whose simulation already
  reached u; it is the bottom of the merge and never changes back.
- Each of K rounds estimates every vertex's marginal influence from its
  registers (harmonic mean, scaled by the share of its simulations still
  open), commits the best vertex, closes the visited set under live edges,
  and, where the score grew by more than ``rebuild_threshold`` of itself
  since the last rebuild, refills the open registers and propagates again.

The sample space is split into column blocks, one per device; the blocks
meet only in the per-round sums (estimates and the visited count).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

VISITED = -1
C_HARMONIC = 1.0 / math.log(2.0)
EDGE_CHUNK = 2048

_M1, _M2, _GOLD = 0x85EBCA6B, 0xC2B2AE35, 0x9E3779B9


def _mix32(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(_M1)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(_M2)
    return x ^ (x >> 16)


def edge_hash(src, dst, seed: int):
    u, v = src.astype(jnp.uint32), dst.astype(jnp.uint32)
    h = _mix32(u * jnp.uint32(_GOLD) + jnp.uint32(seed))
    return _mix32(h ^ (v * jnp.uint32(_M1) + jnp.uint32(0x27D4EB2F)))


def register_hash(u, j, seed: int):
    u, j = u.astype(jnp.uint32), j.astype(jnp.uint32)
    return _mix32(_mix32(u * jnp.uint32(_GOLD) + jnp.uint32(seed ^ 0x5BD1E995))
                  ^ (j * jnp.uint32(_M2)))


def thresholds(weight: np.ndarray) -> np.ndarray:
    """w -> round(w * 2**32), saturated to uint32."""
    t = np.round(np.asarray(weight, np.float64) * 4294967296.0)
    return np.minimum(t, 4294967295.0).astype(np.uint32)


def _estimate(m, total_regs: int, dtype):
    """Per-vertex marginal-influence estimate from this block's registers,
    summed over all blocks."""
    valid = m != VISITED
    terms = jnp.where(valid, jnp.exp2(-m.astype(dtype)), jnp.zeros((), dtype))
    stat = jax.lax.psum(jnp.sum(terms, axis=-1, dtype=dtype), "c")
    cnt = jax.lax.psum(jnp.sum(valid, axis=-1).astype(dtype), "c")
    est = dtype(C_HARMONIC) * cnt / jnp.maximum(stat, dtype(1e-30))
    est = jnp.where(cnt > 0, est * (cnt / dtype(total_regs)), 0)
    return est.astype(jnp.float32)


def _body(src, dst, h, thr, x, forced, *, n, total_regs, k, hash_seed,
          max_prop, max_casc, rebuild_threshold, use_forced, dtype):
    jb = x.shape[0]
    j0 = jax.lax.axis_index("c") * jb
    cols = (jnp.arange(jb) + j0).astype(jnp.uint32)
    rows = jnp.arange(n, dtype=jnp.uint32)
    fresh = jax.lax.clz(register_hash(rows[:, None], cols[None, :],
                                      hash_seed)).astype(jnp.int8)
    chunks = (src, dst, h, thr)

    def live(hh, t):
        return (hh[:, None] ^ x[None, :]) < t[:, None]

    def propagate(m):
        def step(acc, e):
            s, d, hh, t = e
            return acc.at[s].max(jnp.where(live(hh, t), m[d],
                                           jnp.int8(VISITED))), None
        acc, _ = jax.lax.scan(step, m, chunks)
        return jnp.where(m == VISITED, m, acc)

    def cascade(m):
        vis = (m == VISITED).astype(jnp.uint8)

        def step(acc, e):
            s, d, hh, t = e
            return acc.at[d].max(live(hh, t).astype(jnp.uint8) & vis[s]), None
        acc, _ = jax.lax.scan(step, vis, chunks)
        return jnp.where(acc > 0, jnp.int8(VISITED), m)

    def fixpoint(m, sweep, max_sweeps):
        def cond(c):
            return jnp.logical_and(c[1], c[2] < max_sweeps)

        def body(c):
            m_new = sweep(c[0])
            return m_new, jnp.any(m_new != c[0]), c[2] + 1

        m, _, sweeps = jax.lax.while_loop(
            cond, body, (m, jnp.bool_(True), jnp.int32(0)))
        return m, sweeps

    m, build_sweeps = fixpoint(fresh, propagate, max_prop)

    def round_fn(carry, s_forced):
        m, oldscore = carry
        est = _estimate(m, total_regs, dtype)
        best, own = jnp.max(est), jnp.argmax(est).astype(jnp.int32)
        s = s_forced if use_forced else own
        m = m.at[s].set(jnp.int8(VISITED))
        m, _ = fixpoint(m, cascade, max_casc)
        visited = jax.lax.psum(jnp.sum((m == VISITED).astype(jnp.int32)), "c")
        score = visited.astype(jnp.float32) / jnp.float32(total_regs)
        rel = (score - oldscore) / jnp.maximum(score, 1e-9)
        rebuild = rel > rebuild_threshold

        def refill(m):
            m, _ = fixpoint(jnp.where(m == VISITED, m, fresh), propagate,
                            max_prop)
            return m, score

        m, oldscore = jax.lax.cond(rebuild, refill, lambda m: (m, oldscore), m)
        return (m, oldscore), (s, est[s], best, score, rebuild)

    _, outs = jax.lax.scan(round_fn, (m, jnp.float32(0.0)), forced, length=k)
    return outs + (jax.lax.pmax(build_sweeps, "c"),)


@partial(jax.jit, static_argnames=("mesh", "n", "total_regs", "k", "hash_seed",
                                   "max_prop", "max_casc", "rebuild_threshold",
                                   "use_forced", "dtype"))
def _run(src, dst, h, thr, x, forced, *, mesh, **kw):
    rep = P()
    fn = jax.shard_map(partial(_body, **kw), mesh=mesh,
                       in_specs=(rep, rep, rep, rep, P("c"), rep),
                       out_specs=(rep,) * 6, check_vma=False)
    return fn(src, dst, h, thr, x, forced)


def run(edges, cfg: dict, x: np.ndarray, devices, *, seeds=None,
        dtype=jnp.float32) -> dict:
    """Seed selection on ``edges`` with sample vector ``x``, under the
    configuration's ``k`` and its ``spec`` (hash ``seed``, sweep caps,
    ``rebuild_threshold``).

    ``seeds`` (int[K]) commits the given vertices instead of the
    reference's own choice, so a run can be followed round by round.
    ``dtype`` is the precision of the estimator. Returns numpy arrays
    ``seeds, est_at_seed, best, scores, rebuilds`` (one entry per round) and
    the int ``build_sweeps`` of the first fixpoint."""
    k, spec = int(cfg["k"]), cfg["spec"]
    x = np.sort(np.asarray(x, np.uint32))
    if x.shape[0] % len(devices):
        raise ValueError(f"{x.shape[0]} registers do not split over "
                         f"{len(devices)} devices")
    pad = (-edges.m) % EDGE_CHUNK
    src = np.pad(edges.src, (0, pad)).reshape(-1, EDGE_CHUNK)
    dst = np.pad(edges.dst, (0, pad)).reshape(-1, EDGE_CHUNK)
    thr = np.pad(thresholds(edges.weight), (0, pad)).reshape(-1, EDGE_CHUNK)
    seed = int(spec["seed"])
    h = edge_hash(jnp.asarray(src), jnp.asarray(dst), seed)
    forced = np.zeros(k, np.int32) if seeds is None else np.asarray(seeds,
                                                                    np.int32)
    out = _run(jnp.asarray(src), jnp.asarray(dst), h, jnp.asarray(thr),
               jnp.asarray(x), jnp.asarray(forced),
               mesh=Mesh(np.asarray(devices), ("c",)), n=edges.n,
               total_regs=int(x.shape[0]), k=k, hash_seed=seed,
               max_prop=int(spec["max_propagate_iters"]),
               max_casc=int(spec["max_cascade_iters"]),
               rebuild_threshold=float(spec["rebuild_threshold"]),
               use_forced=seeds is not None, dtype=dtype)
    s, est_s, best, scores, rebuilds, sweeps = jax.device_get(out)
    return {"seeds": s, "est_at_seed": est_s, "best": best, "scores": scores,
            "rebuilds": rebuilds, "build_sweeps": int(sweeps)}
