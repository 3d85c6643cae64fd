"""The harness: BENCHMARK.json against the contract, cells found by name, a
metric that is only a file, the result line, and no run without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import compare, harness
from bench.conftest import ROOT, mesh_cell, run_cell, tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and "\n" not in text \
        and "\t" not in text


def test_benchmark_json_keys_names_and_units():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    rs = SPEC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert 2 + 14 * 24 * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = [e["name"] for e in SPEC["configs"] + SPEC["workloads"]] + \
        [m["name"] for m in metrics]
    for section in ("configs", "workloads"):
        assert len({e["name"] for e in SPEC[section]}) == len(SPEC[section])
    assert len({m["name"] for m in metrics}) == len(metrics)
    for name in names:
        assert NAME.match(name), name
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")


def test_configs_and_workloads():
    configs = {c["name"]: c for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("bench/configs/")
        body = json.loads((ROOT / c["file"]).read_text())
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and key in body and key in body["reduced"]
        assert (ROOT / "bench" / "references" /
                f"{body['reference']}.py").is_file()
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == set(configs)
    pairs = {(w["config"], w["traffic"]) for w in SPEC["workloads"]}
    assert len(pairs) == len(SPEC["workloads"])
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(SPEC["workloads"]) // 2)
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()


def test_metrics_sources_bounds_and_readers():
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"]) and m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for cell in cells:
        reported = [m for m in SPEC["per_layer"]
                    if cell in m.get("workloads", cells)]
        assert reported, cell


def test_cells_found_by_name():
    batch = harness.load_cell("batch-s18")
    assert (batch.chips, batch.config["scale"]) == (1, 18)
    assert batch.config["spec"]["backend"] == "single"
    assert batch.traffic["loop"] == "closed"
    assert {m["name"] for m in batch.end_to_end} == {"job_s", "setup_s"}
    assert {m["name"] for m in batch.per_layer} == {
        "build_s", "build_sweeps", "round_s", "sweep_roofline",
        "device_idle.batch"}
    mesh = mesh_cell()
    assert (mesh.chips, mesh.config["spec"]["backend"]) == (4, "mesh")
    assert mesh.config["spec"]["partition"] == "degree"
    for m in batch.per_layer:
        assert callable(harness.metric_reader(m["name"]))
    for name in ("ring_exposed.mesh", "plan_s.mesh"):
        assert callable(harness.metric_reader(name))
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell")


def test_a_metric_that_is_only_a_file_is_read(tmp_path, monkeypatch,
                                               fresh_jax):
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("test_*", "__pycache__"))
    (bench / "metrics" / "job_host_s.py").write_text(
        "def read(r):\n    return r.job_s\n")
    monkeypatch.setattr(harness, "BENCH", bench)
    spec = json.loads(json.dumps(SPEC))
    spec["per_layer"].append({
        "name": "job_host_s", "unit": "s", "better": "lower",
        "source": "host_clock", "layer": "core driver seed rounds",
        "moves": "job_s", "workloads": ["batch-s18"]})
    cell = harness.load_cell("batch-s18", spec)
    cell.config.update(scale=8, k=4)
    cell.config["spec"]["num_registers"] = 128
    line, _ = run_cell(cell, trace=True)
    assert line["correct"] is True
    assert line["metrics"]["job_host_s"]["value"] > 0
    assert line["metrics"]["job_host_s"]["unit"] == "s"


def test_result_line_and_checks_on_stderr(fresh_jax):
    line, err = run_cell(tiny("batch-s18"))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"job_s", "setup_s"}
    assert line["device"]["count"] >= 1
    assert list(line["checks"]) == list(compare.NAMES)
    tail = err.strip().splitlines()[-len(compare.NAMES):]
    for name, text in zip(compare.NAMES, tail):
        assert text.startswith(f"check {name} ") and " limit " in text


def test_draw_x_is_stratified_and_fixed_by_seed():
    """The sample vector is fixed by (seed, job) and uniform over
    [0, 2**32), like the program's own draw: its quarters hold equal shares
    up to sampling noise."""
    a = harness.draw_x(2**31 + 9, 3, 1024)
    assert np.array_equal(a, harness.draw_x(2**31 + 9, 3, 1024))
    assert not np.array_equal(a, harness.draw_x(2**31 + 9, 4, 1024))
    assert not np.array_equal(a, harness.draw_x(2**31 + 10, 3, 1024))
    assert a.dtype == np.uint32
    big = harness.draw_x(2**31 + 9, 3, 1 << 16)
    quarters = np.bincount(big >> np.uint32(30), minlength=4)
    assert np.all(np.abs(quarters - (1 << 14)) < 600)
    assert big.max() > 2**32 - 2**20 and big.min() < 2**20


def test_measuring_run_without_tpu_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "batch-s18", "--seed",
         "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "no TPU" in proc.stderr
