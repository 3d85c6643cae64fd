"""Graph500 Kronecker graphs, generated on the device from a seed.

The edge draw follows the octave reference generator of the Graph500
specification (``kronecker_generator.m``): for each of ``scale`` levels, an
edge's row bit is 1 with probability ``1 - (a + b)`` and its column bit is 1
with probability ``c / (c + d)`` in the lower half and ``b / (a + b)`` in the
upper half; the vertex labels are then randomly permuted (``relabel``).

The graph is undirected, as Graph500 kernel 1 and the GAP suite's ``kron``
graph read it: each distinct unordered pair {u, v}, u != v, of the edge list
becomes the two arcs (u, v) and (v, u), each with the influence setting's
constant weight. Duplicate pairs and self loops are dropped (kernel 1 may
drop them), so the program is handed a simple symmetric graph. All of it
runs in one jitted call on the device.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class EdgeList:
    """A simple symmetric graph: no self loops, no duplicate (src, dst),
    (v, u) present wherever (u, v) is."""

    n: int
    src: np.ndarray      # int32[m], sorted by (src, dst)
    dst: np.ndarray      # int32[m]
    weight: np.ndarray   # float32[m] influence probability per edge

    @property
    def m(self) -> int:
        return int(self.src.shape[0])


@partial(jax.jit, static_argnames=("scale", "edge_factor", "a", "b", "c",
                                   "relabel"))
def _kronecker_arcs(key, *, scale, edge_factor, a, b, c, relabel):
    n, m = 1 << scale, (1 << scale) * edge_factor
    ab = a + b
    c_norm, a_norm = c / (1.0 - ab), a / ab
    keys = jax.random.split(key, scale + 1)
    src = jnp.zeros(m, jnp.int32)
    dst = jnp.zeros(m, jnp.int32)
    for level in range(scale):
        r = jax.random.uniform(keys[level], (2, m))
        row = r[0] > ab
        col = r[1] > jnp.where(row, c_norm, a_norm)
        src = src | (row.astype(jnp.int32) << level)
        dst = dst | (col.astype(jnp.int32) << level)
    if relabel:
        perm = jax.random.permutation(keys[scale], n).astype(jnp.int32)
        src, dst = perm[src], perm[dst]
    # unordered pairs; a self loop or a repeated pair becomes (n, n), which
    # sorts last and is cut on the host
    lo, hi = jnp.minimum(src, dst), jnp.maximum(src, dst)
    loop = lo == hi
    lo, hi = jax.lax.sort((jnp.where(loop, n, lo), jnp.where(loop, n, hi)),
                          num_keys=2)
    first = jnp.concatenate([jnp.ones(1, bool),
                             (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])])
    keep = first & (lo < n)
    lo, hi = jnp.where(keep, lo, n), jnp.where(keep, hi, n)
    arc_src, arc_dst = jax.lax.sort((jnp.concatenate([lo, hi]),
                                     jnp.concatenate([hi, lo])), num_keys=2)
    return arc_src, arc_dst, 2 * jnp.sum(keep.astype(jnp.int32))


def kronecker(*, scale: int, edge_factor: int, a: float, b: float, c: float,
              relabel: bool, setting: float, graph_seed: int) -> EdgeList:
    """The simple weighted graph of one configuration (deterministic in
    ``graph_seed``; the same on every platform)."""
    arc_src, arc_dst, m = jax.device_get(_kronecker_arcs(
        jax.random.key(graph_seed), scale=scale, edge_factor=edge_factor,
        a=a, b=b, c=c, relabel=relabel))
    m = int(m)
    return EdgeList(n=1 << scale, src=arc_src[:m].astype(np.int32),
                    dst=arc_dst[:m].astype(np.int32),
                    weight=np.full(m, setting, np.float32))


def for_config(cfg: dict) -> EdgeList:
    return kronecker(scale=cfg["scale"], edge_factor=cfg["edge_factor"],
                     a=cfg["a"], b=cfg["b"], c=cfg["c"],
                     relabel=cfg["relabel"], setting=cfg["setting"],
                     graph_seed=cfg["graph_seed"])
