"""Compile the main path for a described TPU v5e chip (no chip needed).

Each test lowers a kernel or the jitted seed-selection program with operand
shapes placed on a device of a described ``v5e:2x2`` topology and compiles
it with the TPU compiler, which refuses what the chip would refuse: a block
that breaks the tiling rules, more VMEM than a core has, a program larger
than HBM. Nothing runs, so these say nothing about results or times.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library at a time, and every test worker
imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

# repro.core first: the kernel modules import repro.core.sampling, whose
# package init reaches back into repro.kernels.ops
from repro.core.difuser import _find_seeds_jit, resolve_model
from repro.kernels.bucket_propagate import bucket_propagate_pallas
from repro.kernels.cascade_step import cascade_sweep_pallas
from repro.kernels.common import max_pane_rows
from repro.kernels.fused_sweep import fused_sweep_pallas
from repro.kernels.sketch_cardinality import cardinality_stats_pallas
from repro.kernels.sketch_fill import sketch_fill_pallas
from repro.kernels.sketch_propagate import propagate_sweep_pallas

HBM_BYTES = 16 * 10**9       # one v5e chip
J, LANE, EDGE_BLOCK = 1024, 128, 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent cache
    # but never read back without one: keep these out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]


def _edge_kernel_args(sharding, n_pad, num_regs, num_edges):
    e = ((num_edges,), jnp.int32)
    u = ((num_edges,), jnp.uint32)
    return _shapes(sharding, ((n_pad, num_regs), jnp.int8), e, e, u,
                   ((num_regs,), jnp.uint32), u, u)


@pytest.mark.parametrize("kernel", ["propagate_sweep", "cascade_sweep"])
def test_sweep_kernel_compiles_at_largest_n_pad(one_chip, kernel):
    fn = {"propagate_sweep": propagate_sweep_pallas,
          "cascade_sweep": cascade_sweep_pallas}[kernel]
    n_pad = max_pane_rows(2, LANE)
    args = _edge_kernel_args(one_chip, n_pad, J, 1 << 16)
    compiled = fn.lower(*args, edge_block=EDGE_BLOCK, reg_tile=LANE).compile()
    assert "tpu_custom_call" in compiled.as_text()
    with pytest.raises(ValueError, match="accepts at most"):
        fn.lower(*_edge_kernel_args(one_chip, n_pad + 32, J, 1 << 16),
                 edge_block=EDGE_BLOCK, reg_tile=LANE)


def test_fused_sweep_compiles_at_largest_n_pad(one_chip):
    args = _edge_kernel_args(one_chip, max_pane_rows(3, LANE), J, 1 << 16)
    compiled = fused_sweep_pallas.lower(*args, num_sweeps=2,
                                        lane_tile=LANE).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fill_and_cardinality_compile(one_chip):
    # rmat scale 20's n_pad (n + 1 sentinel, rounded to 8): not a multiple
    # of the (32, 128) int8 tile, so the row padding is exercised
    (m,) = _shapes(one_chip, (((1 << 20) + 8, J), jnp.int8))
    for fn in (sketch_fill_pallas, cardinality_stats_pallas):
        assert "tpu_custom_call" in fn.lower(m).compile().as_text()


def test_bucket_propagate_compiles_at_four_chip_shard(one_chip):
    """A shard of rmat scale 18 on a 2 x 2 mesh (mu_v = mu_s = 2): n_loc =
    n_pad / 2 rows, J / 2 registers. Scale 20's shard (n_loc = 524,292)
    is past the kernel's VMEM bound and must say so."""
    def args(n_loc, bucket=1 << 18):
        e = ((bucket,), jnp.int32)
        u = ((bucket,), jnp.uint32)
        pane = ((n_loc, J // 2), jnp.int8)
        return _shapes(one_chip, pane, pane, u, e, e, u,
                       ((J // 2,), jnp.uint32))

    n_loc = ((1 << 18) + 8) // 2
    assert n_loc <= max_pane_rows(3, LANE)
    compiled = bucket_propagate_pallas.lower(
        *args(n_loc), edge_block=EDGE_BLOCK, reg_tile=LANE).compile()
    assert "tpu_custom_call" in compiled.as_text()
    with pytest.raises(ValueError, match="accepts at most"):
        bucket_propagate_pallas.lower(*args(((1 << 20) + 8) // 2),
                                      edge_block=EDGE_BLOCK, reg_tile=LANE)


def test_find_seeds_fits_one_chip_at_rmat20(one_chip):
    """The default main path (single backend, impl="ref") at Graph500 R-MAT
    scale 20, J = 1024, K = 50 fits one chip's HBM."""
    n = 1 << 20
    n_pad, m_pad = n + 8, n * 16
    e = ((m_pad,), jnp.int32)
    u = ((m_pad,), jnp.uint32)
    args = _shapes(one_chip, e, e, u, u, u, ((J,), jnp.uint32))
    compiled = _find_seeds_jit.lower(
        *args, n_pad=n_pad, k=50, n_real=n, num_regs=J, seed=0,
        estimator="hll", impl="ref", edge_chunk=2048, max_prop=64,
        max_casc=64, rebuild_threshold=0.01,
        predicate=resolve_model("wc").predicate, cascade_chunk=0,
        edge_block=0, reg_tile=0).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 1e9:.2f} GB does not fit one chip"


def test_find_seeds_scatters_carry_their_phase_scope(one_chip):
    """Each sweep's scatter-max in the chip's compiled job names exactly one
    of the job's phases in its op metadata, and the first fixpoint and the
    rebuild each have theirs: a device trace can split the job's time by
    phase. The cascade has no scatter on the register shape: its sweep pulls
    through a product and one window write per chunk, and those ops carry
    ``difuser.cascade``."""
    import re

    n = 1 << 12
    n_pad = n + 8
    e = ((1 << 16,), jnp.int32)
    u = ((1 << 16,), jnp.uint32)
    args = _shapes(one_chip, e, e, u, u, u, ((J,), jnp.uint32))
    text = _find_seeds_jit.lower(
        *args, n_pad=n_pad, k=4, n_real=n, num_regs=J, seed=0,
        estimator="hll", impl="ref", edge_chunk=2048, max_prop=64,
        max_casc=64, rebuild_threshold=0.01,
        predicate=resolve_model("wc").predicate).compile().as_text()
    scopes = ("difuser.fixpoint", "difuser.select", "difuser.cascade",
              "difuser.rebuild")
    ops = []   # (dims, opcode, op_name) of every instruction with metadata
    for line in text.splitlines():
        instr = re.search(r"= \w+\[([\d,]*)\]\S* ([\w-]+)\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if instr and name:
            ops.append((instr.group(1), instr.group(2), name.group(1)))
    seen = set()
    for _, _, name in ops:
        if name.endswith("scatter-max"):
            named = [s for s in scopes if s in name]
            assert len(named) <= 1, name
            seen.update(named)
    assert seen == {"difuser.fixpoint", "difuser.rebuild"}
    register = f"{n_pad},{J}"
    cascade = [(dims, opcode, name) for dims, opcode, name in ops
               if "difuser.cascade" in name]
    assert not [op for op in cascade
                if op[0] == register and "scatter" in op[1]]
    sweep = [op for op in cascade if "cascade_sweep_ref" in op[2]]
    assert any(name.endswith("dot_general") for _, _, name in sweep)
    assert any(dims == register and name.endswith("dynamic_update_slice")
               for dims, _, name in sweep)
