"""Fixtures of the benchmark's own tests: cells cut to a size the CPU runs
in seconds, and a runner that drives a whole run without a chip."""
import io
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

from bench import harness

ROOT = Path(__file__).resolve().parent.parent


#: the four-chip configuration, tested here whether or not a cell of
#: ``BENCHMARK.json`` names it
MESH_CONFIG = "bench/configs/g500-s19-w1-hubids.json"


def mesh_cell():
    """A cell of the 2 x 2 configuration under the ``jobs`` mix."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "mesh-config", "file": MESH_CONFIG})
    spec["workloads"].append({"name": "mesh-test", "config": "mesh-config",
                              "traffic": "jobs", "chips": 4})
    return harness.load_cell("mesh-test", spec)


def tiny(name: str, *, scale: int = 8, registers: int = 128, k: int = 4):
    """Cell ``name`` of ``BENCHMARK.json`` (``"mesh"``: :func:`mesh_cell`)
    at a test size."""
    cell = mesh_cell() if name == "mesh" else harness.load_cell(name)
    cell.config["scale"] = scale
    cell.config["spec"]["num_registers"] = registers
    cell.config["k"] = k
    return cell


def run_cell(cell, *, seed=2**31 + 5, seconds=0.2, trace=False):
    """One run without the look for a chip; returns (result line, stderr)."""
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(cell, seed=seed, seconds=seconds, trace=trace,
                     t_start=time.perf_counter(), require_tpu=False,
                     out=out, err=err)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()


@pytest.fixture
def fresh_jax(monkeypatch):
    """No persistent cache and no traced program left over: a test that
    breaks the program underneath must trace it anew."""
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    jax.clear_caches()
    yield
    jax.clear_caches()


def run_on_four_cpu_devices(code: str, timeout: int = 600) -> list:
    """Run ``code`` in a child process with four CPU devices; returns the
    JSON objects it printed, one per line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return [json.loads(line) for line in proc.stdout.splitlines()
            if line.startswith("{")]
