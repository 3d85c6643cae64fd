"""End-to-end DiFuseR quality and behavior (paper Tables 3/4 claims)."""
import numpy as np
import pytest

from repro.baselines import exact_greedy, influence_score, ris_find_seeds
from repro.core.difuser import DiFuserConfig, find_seeds
from repro.graphs import erdos_renyi_graph, rmat_graph


def test_quality_vs_exact_greedy_supercritical():
    """DiFuseR's seed set reaches >=90% of exact-greedy influence in the
    paper's regime (supercritical cascades, spreads in the hundreds+)."""
    g = erdos_renyi_graph(300, avg_degree=14, seed=11, setting="w1")
    k = 5
    res = find_seeds(g, k, DiFuserConfig(num_registers=256, seed=1))
    _, greedy_score = exact_greedy(g, k, num_sims=120, rng_seed=5)
    ours = influence_score(g, res.seeds, num_sims=300, rng_seed=6)
    assert ours >= 0.90 * greedy_score, (ours, greedy_score)


def test_quality_vs_exact_greedy_subcritical():
    """Subcritical micro-spreads (each seed reaches ~3 vertices) are the FM
    sketch's known weak spot (clz granularity at cardinality < 8); DiFuseR
    still lands within 85% of exact greedy. The paper's graphs (spreads of
    1e3..1e7) don't hit this regime — documented, not hidden."""
    g = erdos_renyi_graph(300, avg_degree=6, seed=11, setting="w1")
    res = find_seeds(g, 5, DiFuserConfig(num_registers=256, seed=1))
    _, greedy_score = exact_greedy(g, 5, num_sims=120, rng_seed=5)
    ours = influence_score(g, res.seeds, num_sims=300, rng_seed=6)
    assert ours >= 0.85 * greedy_score, (ours, greedy_score)


def test_quality_vs_ris():
    g = rmat_graph(9, edge_factor=8, seed=12, setting="w1")
    k = 8
    res = find_seeds(g, k, DiFuserConfig(num_registers=256, seed=0))
    ris_seeds, _ = ris_find_seeds(g, k, num_rr_sets=3000, rng_seed=3)
    ours = influence_score(g, res.seeds, num_sims=200, rng_seed=7)
    ris = influence_score(g, ris_seeds, num_sims=200, rng_seed=7)
    assert ours >= 0.92 * ris, (ours, ris)


def test_internal_score_matches_oracle():
    """DiFuseR's own influence estimate (visited count / J) is close to the
    independent Monte-Carlo oracle (paper §5.1 oracle validation)."""
    g = rmat_graph(9, edge_factor=8, seed=13, setting="w1")
    res = find_seeds(g, 5, DiFuserConfig(num_registers=512, seed=2))
    oracle = influence_score(g, res.seeds, num_sims=300, rng_seed=8)
    rel = abs(res.scores[-1] - oracle) / max(oracle, 1.0)
    assert rel < 0.15, (res.scores[-1], oracle)


def test_scores_monotone_in_k():
    g = rmat_graph(8, edge_factor=8, seed=14, setting="u01")
    res = find_seeds(g, 10, DiFuserConfig(num_registers=128, seed=3))
    assert (np.diff(res.scores) >= -1e-6).all()
    assert len(set(res.seeds.tolist())) == 10, "seeds must be distinct"


def test_lazy_rebuild_threshold():
    """e=inf never rebuilds; e=0 rebuilds whenever the score moves."""
    g = rmat_graph(8, edge_factor=8, seed=15, setting="w1")
    never = find_seeds(g, 6, DiFuserConfig(num_registers=128, seed=4,
                                           rebuild_threshold=float("inf")))
    always = find_seeds(g, 6, DiFuserConfig(num_registers=128, seed=4,
                                            rebuild_threshold=0.0))
    assert never.rebuilds.sum() == 0
    assert always.rebuilds.sum() >= 5
    # rebuilding can only help quality (within estimator noise)
    assert always.scores[-1] >= 0.85 * never.scores[-1]


def test_more_registers_better_estimates():
    g = rmat_graph(8, edge_factor=8, seed=16, setting="w1")
    small = find_seeds(g, 5, DiFuserConfig(num_registers=32, seed=5))
    big = find_seeds(g, 5, DiFuserConfig(num_registers=512, seed=5))
    o_small = influence_score(g, small.seeds, num_sims=200, rng_seed=9)
    o_big = influence_score(g, big.seeds, num_sims=200, rng_seed=9)
    assert o_big >= 0.95 * o_small


def test_pallas_impl_end_to_end():
    """The full driver also runs with the Pallas kernels (interpreted on
    CPU)."""
    g = rmat_graph(7, edge_factor=6, seed=17, setting="w1")
    ref = find_seeds(g, 3, DiFuserConfig(num_registers=128, seed=6, impl="ref"))
    pal = find_seeds(g, 3, DiFuserConfig(num_registers=128, seed=6, impl="pallas"))
    np.testing.assert_array_equal(ref.seeds, pal.seeds)
    np.testing.assert_allclose(ref.scores, pal.scores, rtol=1e-6)
