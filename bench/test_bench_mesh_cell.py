"""The four-chip cell ``mesh-s19`` through the harness on four CPU devices,
cut to a test size: the run is correct, its window compiles nothing after
the warm-up, and its readers find what the program and the trace record."""
import pytest

from bench import harness, peaks, tracereduce as tr
from bench.conftest import run_on_four_cpu_devices

MS = 1_000_000  # ns

_RUNS = """
import io, json, time
from bench import harness
harness.enable_compile_cache = lambda: None


def run(trace):
    cell = harness.load_cell("mesh-s19")
    cell.config.update(scale=8, k=4)
    cell.config["spec"]["num_registers"] = 128
    out, err = io.StringIO(), io.StringIO()
    harness.run(cell, seed=2**31 + 11, seconds=0.1, trace=trace,
                t_start=time.perf_counter(), require_tpu=False, out=out,
                err=err)
    window = [s for s in err.getvalue().splitlines()
              if s.startswith("bench: window")]
    return {"line": json.loads(out.getvalue().splitlines()[-1]),
            "window": window}


print(json.dumps({"case": "untraced", **run(False)}))
print(json.dumps({"case": "traced", **run(True)}))
"""


@pytest.fixture(scope="module")
def runs():
    return {row["case"]: row for row in run_on_four_cpu_devices(_RUNS)}


def test_mesh_cell_run_is_correct(runs):
    line = runs["untraced"]["line"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == 4
    assert set(line["metrics"]) == {"job_s", "setup_s"}
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())


def test_mesh_cell_window_compiles_nothing(runs):
    (window,) = runs["untraced"]["window"]
    assert "; compiled 0," in window, window


def test_mesh_cell_traced_run_reads_the_partition(runs):
    line = runs["traced"]["line"]
    assert line["correct"] is True
    pad = line["metrics"]["bucket_pad_share.mesh"]
    assert pad["unit"] == "%" and 0 <= pad["value"] < 100
    # the CPU backend has no device plane: the device-trace readers are
    # silent here and read on the chip
    for name in ("ring_exposed.mesh", "device_idle.mesh",
                 "sweep_roofline.mesh"):
        assert name not in line["metrics"], name


def _two_chips():
    """Chip 0 computes 600 ms; chip 1 computes 300 ms and then waits 100 ms
    on a ring exchange that nothing overlaps, all inside a ``bench.build``
    of 1000 ms; the window is 1500 ms."""
    ops = {"/device:TPU:0": [tr.Event("fusion.1", 100 * MS, 400 * MS),
                             tr.Event("fusion.2", 500 * MS, 800 * MS)],
           "/device:TPU:1": [tr.Event("fusion.1", 100 * MS, 400 * MS),
                             tr.Event("collective-permute-done.1", 800 * MS,
                                      900 * MS)]}
    host = [tr.Event("bench.build", 0, 1000 * MS),
            tr.Event("bench.job", 1000 * MS, 1500 * MS)]
    return tr.Reduction(tr.TraceData(devices=ops, host=host),
                        window=(0, 1500 * MS))


def _readings(spans=(), trace=None, chip=None):
    return harness.Readings(
        config={"k": 4}, n=256, m=4096, registers=128, build_s=1.0,
        job_s=0.5, answer={}, ref_build_sweeps=5, program_spans=list(spans),
        trace=trace, peaks=chip)


V5E = peaks.for_kind("TPU v5 lite")


@pytest.mark.parametrize("name, expected", [
    ("device_idle.mesh", 100.0 * (1 - 0.5 / 1.5)),
    ("ring_exposed.mesh", 100.0 * (0 + 0.1 / 1.5) / 2),
    # 5 sweeps of 2 n J + 20 m bytes at two chips' bandwidth, over the
    # 0.5 s the two chips were busy on average inside the build
    ("sweep_roofline.mesh",
     100.0 * 5 * (2 * 256 * 128 + 20 * 4096) / (2 * V5E.hbm_bw) / 0.5),
])
def test_device_readers_on_a_two_chip_trace(name, expected):
    value = harness.metric_reader(name)(_readings(trace=_two_chips(),
                                                  chip=V5E))
    assert value == pytest.approx(expected)


@pytest.mark.parametrize("name", ["device_idle.mesh", "ring_exposed.mesh",
                                  "sweep_roofline.mesh"])
def test_device_readers_are_silent_without_a_trace(name):
    assert harness.metric_reader(name)(_readings(chip=V5E)) is None


def test_pad_share_reader_reads_the_job_span():
    read = harness.metric_reader("bucket_pad_share.mesh")
    span = {"name": "mesh.find_seeds", "attrs": {"bucket_pad_share": 0.0125}}
    assert read(_readings([span])) == pytest.approx(1.25)
    # a program without the attribute (the parent of this reader)
    assert read(_readings([{"name": "mesh.find_seeds", "attrs": {}}])) is None
    assert read(_readings()) is None
