"""Launcher CLIs + fault-tolerance supervisor behavior."""
import sys


def test_im_cli_end_to_end(capsys):
    from repro.launch.im import run

    out = run(["--graph", "rmat:8", "--setting", "0.1", "--k", "5",
               "--registers", "128", "--validate"])
    assert out["difuser_score"] > 0
    assert out["oracle_score"] > 0
    rel = abs(out["difuser_score"] - out["oracle_score"]) / out["oracle_score"]
    assert rel < 0.25


def test_compile_cache_dir(monkeypatch, tmp_path):
    """The launchers' cache: JAX's own directory when
    JAX_COMPILATION_CACHE_DIR is set, else the checkout's fixed one."""
    import jax

    from repro.launch.common import COMPILE_CACHE_DIR, enable_compile_cache

    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert enable_compile_cache() == str(COMPILE_CACHE_DIR)
        assert COMPILE_CACHE_DIR.parent.joinpath("chip_smoke.py").exists()
        own = str(tmp_path / "cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", own)
        jax.config.update("jax_compilation_cache_dir", own)  # as JAX reads it
        assert enable_compile_cache() == own
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          saved[1])


def test_ft_supervisor_restarts_until_success(tmp_path):
    """A command that fails twice then succeeds is relaunched transparently."""
    from repro.launch.ft import supervise

    marker = tmp_path / "attempts"
    script = (
        "import sys, pathlib\n"
        f"p = pathlib.Path({str(marker)!r})\n"
        "n = int(p.read_text()) if p.exists() else 0\n"
        "p.write_text(str(n + 1))\n"
        "sys.exit(0 if n >= 2 else 1)\n"
    )
    rc = supervise([sys.executable, "-c", script], max_restarts=5)
    assert rc == 0
    assert marker.read_text() == "3"


def test_ft_supervisor_gives_up(tmp_path):
    from repro.launch.ft import supervise

    rc = supervise([sys.executable, "-c", "import sys; sys.exit(3)"], max_restarts=1)
    assert rc == 3


def test_elastic_snapshot_roundtrip(tmp_path):
    """The FT story's index half: a server relaunch restores the persisted
    SketchStore snapshot (plan included) instead of re-running the cold
    fixpoint, on any topology (host restore here; mesh restore is the
    mesh half of test_sharded_serving.py)."""
    import numpy as np

    from repro.core.difuser import DiFuserConfig
    from repro.graphs import rmat_graph
    from repro.partition import plan_partition
    from repro.service import SketchStore

    g = rmat_graph(7, edge_factor=6, seed=2, setting="w1")
    cfg = DiFuserConfig(num_registers=64, seed=2)
    store = SketchStore()
    e = store.get_or_build(g, cfg)
    store.attach_plan(e.key, plan_partition(e.graph, 4, mu_s=1, x=e.x))
    path = str(tmp_path / "index")
    store.save(path, e.key)
    restored = SketchStore().load(path)
    np.testing.assert_array_equal(np.asarray(restored.matrix),
                                  np.asarray(e.matrix))
    assert restored.plan is not None and restored.plan.mu_v == 4
    assert restored.residency == "host"
