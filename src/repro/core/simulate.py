"""Sketch propagation to fixpoint (paper Alg. 2 + the Alg. 4 lines 5-6 loop).

One sweep max-merges every vertex's registers with its sampled out-
neighbors'; repeating until nothing changes yields, for each simulation j,
``M[u, j] = max clz over the j-sampled reachability set of u``. The sweep
count is bounded by the max diameter of the sampled graphs — for the
power-law graphs the paper targets this is small; ``max_iters`` caps the
pathological case (paper §6 concedes the same limitation for road-type
networks).

The optional (h, lo, predicate) triple is the diffusion-model hook threaded
down to kernels/ops.py; omitted, the legacy weighted-cascade sampling is
reproduced bit-for-bit.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ops


# inline: a program that runs the fixpoint at two sites (the first build and
# the lazy rebuild) gets its own copy at each, so each copy's device ops keep
# the named scope of their site; a shared callee carried both scopes on the
# TPU compiler's op metadata. The compiled code is the same either way.
@partial(jax.jit, static_argnames=("seed", "impl", "edge_chunk", "max_iters",
                                   "predicate", "edge_block", "reg_tile"),
         inline=True)
def propagate_to_fixpoint(m, src, dst, thr, x, h=None, lo=None, *, seed: int = 0,
                          impl: str = "ref", edge_chunk: int = 2048,
                          max_iters: int = 64, predicate=None,
                          edge_block: int = 0, reg_tile: int = 0):
    """Run SIMULATE sweeps until convergence. Returns (m, iters_used).

    ``edge_chunk`` (ref impl) and ``edge_block``/``reg_tile`` (pallas impl,
    0 = kernel default) are performance-only tile knobs — repro.tune feeds
    measured winners through them; results are invariant."""

    def cond(carry):
        _, changed, it = carry
        return jnp.logical_and(changed, it < max_iters)

    def body(carry):
        m_cur, _, it = carry
        m_new = ops.propagate_sweep(m_cur, src, dst, thr, x, seed=seed, impl=impl,
                                    edge_chunk=edge_chunk, h=h, lo=lo,
                                    predicate=predicate, edge_block=edge_block,
                                    reg_tile=reg_tile)
        changed = jnp.any(m_new != m_cur)
        return m_new, changed, it + 1

    m_out, _, iters = jax.lax.while_loop(cond, body, (m, jnp.bool_(True), jnp.int32(0)))
    return m_out, iters
