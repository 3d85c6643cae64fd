"""Influence cascade (paper §3.3, Alg. 3 + Alg. 4 lines 15-19).

Committing a seed ``s`` marks ``M[s, :] = VISITED`` and closes the visited
set under sampled edges: any vertex reachable from the seed set through
j-sampled edges becomes VISITED in simulation j. Because the previous
visited set is already closed, re-closing after adding one seed only
explores the seed's newly-covered region — the same work the paper's
frontier queue does, expressed as masked dense sweeps with a fixpoint early
exit (DESIGN.md §2).

Same (h, lo, predicate) diffusion-model hook as core/simulate.py.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.sketch import VISITED
from repro.kernels import ops


@partial(jax.jit, static_argnames=("seed", "impl", "edge_chunk", "max_iters",
                                   "predicate", "edge_block", "reg_tile"))
def cascade_from_seed(m, seed_vertex, src, dst, thr, x, h=None, lo=None, *,
                      seed: int = 0, impl: str = "ref", edge_chunk: int = 2048,
                      max_iters: int = 64, predicate=None,
                      edge_block: int = 0, reg_tile: int = 0, layout=None):
    """Mark the seed visited in all sims and close under sampled edges.

    Returns (m, iters_used). ``edge_chunk``/``edge_block``/``reg_tile`` are
    performance-only tile knobs (see core.simulate.propagate_to_fixpoint).
    ``layout`` (ref impl): the arcs' ``ops.cascade_layout``; built here,
    once for all sweeps, when omitted.
    """
    m = m.at[seed_vertex, :].set(jnp.int8(VISITED))
    if impl == "ref" and layout is None:
        layout = ops.cascade_layout(src, dst, thr, n_pad=m.shape[0], seed=seed,
                                    edge_chunk=edge_chunk, h=h, lo=lo)

    def cond(carry):
        _, changed, it = carry
        return jnp.logical_and(changed, it < max_iters)

    def body(carry):
        m_cur, _, it = carry
        m_new = ops.cascade_sweep(m_cur, src, dst, thr, x, seed=seed, impl=impl,
                                  edge_chunk=edge_chunk, h=h, lo=lo,
                                  predicate=predicate, edge_block=edge_block,
                                  reg_tile=reg_tile, layout=layout)
        changed = jnp.any(m_new != m_cur)
        return m_new, changed, it + 1

    m_out, _, iters = jax.lax.while_loop(cond, body, (m, jnp.bool_(True), jnp.int32(0)))
    return m_out, iters
