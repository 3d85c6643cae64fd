"""A whole run without the look for a chip, with the timed path broken
underneath: each fault a cell can have, and the control, must come out as
not correct. The same run unbroken comes out correct."""

import numpy as np
import pytest

from bench import compare, graphgen, harness
from bench.conftest import run_cell, run_on_four_cpu_devices, tiny
from bench.control import control_answer


def _broken(line, *names):
    assert line["correct"] is False
    assert line["failed"] >= 1
    over = [k for k, c in line["checks"].items() if c["value"] > c["limit"]]
    assert set(names) & set(over), over


def test_unbroken_run_is_correct(fresh_jax):
    line, _ = run_cell(tiny("batch-s18"))
    assert line["correct"] is True


def test_sweep_that_returns_its_state_unchanged(fresh_jax, monkeypatch):
    from repro.kernels import ops

    monkeypatch.setattr(ops, "propagate_sweep", lambda m, *a, **k: m)
    line, _ = run_cell(tiny("batch-s18"))
    _broken(line, "sweeps_diff", "gain_err")


def test_half_the_registers_left_out_of_the_estimate(fresh_jax, monkeypatch):
    from repro.kernels import ops

    whole = ops.cardinality_stats

    def half(m, **kw):
        return whole(m[:, : m.shape[1] // 2], **kw) * 2.0

    monkeypatch.setattr(ops, "cardinality_stats", half)
    line, _ = run_cell(tiny("batch-s18"))
    _broken(line, "gain_err", "seed_gap")


def test_half_the_edges_left_out_of_the_sweeps(fresh_jax, monkeypatch):
    from repro.kernels import ops

    def first_half(sweep):
        def run(m, src, dst, thr, x, *, h=None, lo=None, **kw):
            e = src.shape[0] // 2
            return sweep(m, src[:e], dst[:e], thr[:e], x, h=h[:e], lo=lo[:e],
                         **kw)
        return run

    monkeypatch.setattr(ops, "propagate_sweep", first_half(ops.propagate_sweep))
    monkeypatch.setattr(ops, "cascade_sweep", first_half(ops.cascade_sweep))
    line, _ = run_cell(tiny("batch-s18"))
    _broken(line, "gain_err", "score_diff", "sweeps_diff")


def test_answer_altered_where_it_is_produced(fresh_jax, monkeypatch):
    """The last round answers the first round's vertex again, a seed with no
    gain left. (A neighbour's id would not do: late rounds pick among small
    components whose estimates can tie with the best to 0.04%, and such an
    answer is as good as the best.)"""
    from repro.core import difuser

    produce = difuser._find_seeds_single

    def altered(g, k, config=None, x=None):
        res = produce(g, k, config, x)
        res.seeds = res.seeds.copy()
        res.seeds[-1] = res.seeds[0]
        return res

    monkeypatch.setattr(difuser, "_find_seeds_single", altered)
    line, _ = run_cell(tiny("batch-s18"))
    _broken(line, "seed_gap", "score_diff", "gain_err")


def test_control_in_the_programs_place(fresh_jax, monkeypatch):
    cell = tiny("batch-s18")
    edges = graphgen.for_config(cell.config)
    ref = harness.reference(cell.config["reference"])

    def control_job(self, x):
        import jax

        return control_answer(ref, edges, cell.config, x, jax.devices()[:1])

    monkeypatch.setattr(harness.System, "job", control_job)
    line, _ = run_cell(cell)
    _broken(line, "gain_err")


def test_control_separates_from_the_reference(fresh_jax):
    """With the cell's 1024 registers on a small graph the bfloat16 control
    already reads ten times a limit or more (it has to fail one of the
    cell's numbers, not each), and the float32 reference itself reads
    nothing."""
    import jax

    cell = tiny("batch-s18", scale=9, registers=1024)
    edges = graphgen.for_config(cell.config)
    ref = harness.reference(cell.config["reference"])
    devices = jax.devices()[:1]
    x = harness.draw_x(11, 1, 1024)
    low = control_answer(ref, edges, cell.config, x, devices)
    readings = compare.compare(
        low, ref.run(edges, cell.config, x, devices, seeds=low["seeds"]),
        edges.n)
    limits = cell.config["limits"]
    assert max(readings[k] / limits[k] for k in ("gain_err", "seed_gap")) > 10
    own = ref.run(edges, cell.config, x, devices)
    assert np.array_equal(own["est_at_seed"],
                          ref.run(edges, cell.config, x, devices,
                                  seeds=own["seeds"])["est_at_seed"])


_MESH = """
import io, json, time, jax
from bench import harness
from bench.conftest import tiny
harness.enable_compile_cache = lambda: None
def run(cell):
    out = io.StringIO()
    harness.run(cell, seed=2**31 + 3, seconds=0.1, trace=False,
                t_start=time.perf_counter(), require_tpu=False, out=out,
                err=io.StringIO())
    return json.loads(out.getvalue().splitlines()[-1])
print(json.dumps({"case": "sound", **run(tiny("mesh"))}))
jax.clear_caches()
jax.lax.ppermute = lambda x, axis_name, perm: x
print(json.dumps({"case": "no exchange", **run(tiny("mesh"))}))
"""


@pytest.fixture(scope="module")
def mesh_runs():
    return {row["case"]: row for row in run_on_four_cpu_devices(_MESH)}


def test_mesh_run_is_correct(mesh_runs):
    assert mesh_runs["sound"]["correct"] is True
    assert mesh_runs["sound"]["device"]["count"] == 4


def test_mesh_exchange_between_chips_left_out(mesh_runs):
    _broken(mesh_runs["no exchange"], "gain_err", "sweeps_diff", "score_diff")
