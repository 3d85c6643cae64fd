"""HLO collective parser units + a miniature dry-run (8 fake devices,
subprocess) covering the IM shard_map cell."""
import json
import os
import subprocess
import sys

import pytest

from repro.utils.hlo import collective_stats
from repro.utils.roofline import Roofline


def test_parser_all_reduce():
    text = ('  %all-reduce.1 = f32[1024]{0} all-reduce(f32[1024]{0} %x), '
            'replica_groups={{0,1,2,3}}, to_apply=%add\n')
    s = collective_stats(text)
    # ring all-reduce: 2 * 4096 B * 3/4
    assert abs(s.wire_bytes - 2 * 4096 * 3 / 4) < 1e-6
    assert s.op_count == 1


def test_parser_all_gather_and_permute():
    text = (
        "%all-gather = bf16[16,128]{1,0} all-gather(bf16[2,128]{1,0} %p), "
        "replica_groups=[1,8]<=[8], dimensions={0}\n"
        "%collective-permute = u8[64]{0} collective-permute(u8[64]{0} %q), "
        "source_target_pairs={{0,1},{1,2}}\n")
    s = collective_stats(text)
    ag = 16 * 128 * 2 * (7 / 8)
    cp = 64
    assert abs(s.wire_bytes - (ag + cp)) < 1e-6
    assert set(s.by_kind) == {"all-gather", "collective-permute"}


def test_parser_skips_async_done():
    text = ("%all-gather-start = f32[8]{0} all-gather(f32[1]{0} %p), replica_groups={{0,1}}\n"
            "%all-gather-done = f32[8]{0} all-gather-done(%all-gather-start)\n")
    s = collective_stats(text)
    assert s.op_count == 1


def test_roofline_terms():
    r = Roofline(arch="x", shape="train_4k", mesh="m", chips=256,
                 flops_per_device=197e12, bytes_per_device=819e9,
                 wire_bytes_per_device=50e9, model_flops_total=197e12 * 256)
    assert abs(r.t_compute - 1.0) < 1e-9
    assert abs(r.t_memory - 1.0) < 1e-9
    assert abs(r.t_collective - 1.0) < 1e-9
    assert r.useful_flops_ratio == 1.0


MINI_DRYRUN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
from repro.launch.mesh import make_mesh
from repro.utils.hlo import collective_stats

out = {}
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))

# IM cell on the mini mesh
from repro.launch.dryrun import lower_im_cell, IM_CELLS
IM_CELLS["mini"] = (1 << 12, 1 << 14, 64, 1.5)
lowered, part = lower_im_cell("mini", mesh)
compiled = lowered.compile()
coll = collective_stats(compiled.as_text())
out["im"] = {"wire": coll.wire_bytes, "ok": True,
             "kinds": sorted(coll.by_kind)}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def mini_dryrun():
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", MINI_DRYRUN], env=env, cwd=root,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_mini_dryrun_im_cell_compiles_with_ring(mini_dryrun):
    im = mini_dryrun["im"]
    assert im["ok"]
    # ring ppermute + selection psum must both be present
    assert "collective-permute" in im["kinds"], im["kinds"]
    assert any(k in im["kinds"] for k in ("all-reduce", "all-gather")), im["kinds"]
