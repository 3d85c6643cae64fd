"""The readers of the program's own spans and counters: each on a made-up
traced run whose numbers are known, None where the program records no such
span or counter (as a program without them does), and all of them on a
traced run of the cell at a test size."""
import json

import pytest

from bench import harness, tracereduce as tr
from bench.conftest import ROOT, run_cell

MS = 1_000_000  # ns

#: the per-layer entries these readers are written for
ENTRIES = [
    {"name": "host_prep_s", "unit": "s", "better": "lower",
     "source": "program_span", "layer": "entry", "moves": "job_s"},
    {"name": "cascade_sweeps", "unit": "sweeps", "better": "lower",
     "source": "program_counter", "layer": "core driver seed rounds",
     "moves": "job_s"},
    {"name": "rebuild_sweeps", "unit": "sweeps", "better": "lower",
     "source": "program_counter", "layer": "core driver seed rounds",
     "moves": "job_s"},
    {"name": "sweep_device_ms", "unit": "ms", "better": "lower",
     "source": "device_trace", "layer": "kernels", "moves": "job_s"},
    {"name": "job_compiles", "unit": "compiles", "better": "lower",
     "source": "program_counter", "layer": "entry", "moves": "job_s"},
]
NAMES = [e["name"] for e in ENTRIES]


def _job_span(**attrs):
    return {"name": "single.find_seeds", "phase": "select", "ts_s": 0.0,
            "dur_s": 2.0, "depth": 0, "id": 1, "parent": None,
            "attrs": {"k": 4, **attrs}}


def _readings(spans, trace=None):
    return harness.Readings(
        config={"k": 4}, n=256, m=4096, registers=128, build_s=1.0,
        job_s=2.0, answer={}, ref_build_sweeps=None, program_spans=spans,
        trace=trace, peaks=None)


def _trace(job=(0, 1000)):
    """A device busy 600 ms inside a ``bench.job`` of 1000 ms (and busy
    outside it, which does not count)."""
    ops = [tr.Event("fusion.1", 100 * MS, 400 * MS),
           tr.Event("fusion.2", 500 * MS, 800 * MS),
           tr.Event("fusion.3", 1100 * MS, 1500 * MS)]
    host = [tr.Event("bench.job", job[0] * MS, job[1] * MS)]
    data = tr.TraceData(devices={"/device:TPU:0": ops}, host=host)
    return tr.Reduction(data, window=(0, 1500 * MS))


FULL = [
    {"name": "entry.normalize_inputs", "dur_s": 0.25, "attrs": {}},
    {"name": "entry.edge_operands", "dur_s": 0.5, "attrs": {}},
    {"name": "partition.plan", "dur_s": 7.0, "attrs": {}},
    _job_span(build_sweeps=3, cascade_sweeps=2, rebuild_sweeps=1,
              compiles=0),
]


@pytest.mark.parametrize("name, expected", [
    ("host_prep_s", 0.75), ("cascade_sweeps", 2.0), ("rebuild_sweeps", 1.0),
    ("job_compiles", 0.0), ("sweep_device_ms", 100.0)])
def test_reader_gives_its_value(name, expected):
    value = harness.metric_reader(name)(_readings(FULL, _trace()))
    assert value == pytest.approx(expected)


@pytest.mark.parametrize("name", NAMES)
def test_reader_is_silent_without_the_programs_spans(name):
    """A program that records only its job span, with no counters on it,
    as the parent of these readers does: every reader returns None."""
    bare = [_job_span()]
    assert harness.metric_reader(name)(_readings(bare, _trace())) is None
    assert harness.metric_reader(name)(_readings([], None)) is None


def test_sweep_device_ms_needs_the_trace_and_the_job_annotation():
    read = harness.metric_reader("sweep_device_ms")
    assert read(_readings(FULL, None)) is None
    no_job = _trace()
    no_job.data.host.clear()
    assert read(_readings(FULL, no_job)) is None
    idle = _trace(job=(1500, 1600))
    assert read(_readings(FULL, idle)) is None


def test_traced_run_reports_the_program_readers(fresh_jax):
    """A traced run of the cell at a test size with these entries added:
    the program's spans and counters reach the readers."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["per_layer"] += [dict(e, workloads=["batch-s18"]) for e in ENTRIES]
    cell = harness.load_cell("batch-s18", spec)
    cell.config.update(scale=8, k=4)
    cell.config["spec"]["num_registers"] = 128
    line, _ = run_cell(cell, trace=True)
    assert line["correct"] is True
    metrics = line["metrics"]
    for name in ("host_prep_s", "cascade_sweeps", "rebuild_sweeps",
                 "job_compiles"):
        assert name in metrics, name
    assert metrics["host_prep_s"]["value"] > 0
    assert metrics["job_compiles"]["value"] == 0
    assert metrics["cascade_sweeps"]["value"] >= cell.config["k"]
    assert metrics["cascade_sweeps"]["unit"] == "sweeps"
    if "sweep_device_ms" in metrics:   # only where the trace has a device
        assert metrics["sweep_device_ms"]["value"] > 0
