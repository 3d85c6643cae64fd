"""Observability layer: spans, recorder, Chrome export, metrics, drivers.

The recorder and registry are process-global, so every test that turns them
on restores the disabled/empty state in a finally block — the rest of the
suite must keep seeing the zero-overhead null path.
"""
import json

import numpy as np
import pytest

from repro.obs import metrics, trace


@pytest.fixture
def recorder():
    rec = trace.get_recorder()
    rec.start()
    try:
        yield rec
    finally:
        rec.stop()
        rec.clear()


# ---------------------------------------------------------------------------
# trace: spans + recorder
# ---------------------------------------------------------------------------


def test_disabled_span_is_shared_null_singleton():
    """With the recorder off, span() allocates nothing: every call returns
    the same null object, and nothing is recorded."""
    rec = trace.get_recorder()
    assert not rec.enabled
    s1 = trace.span("a", phase="build")
    s2 = trace.span("b", x=1)
    assert s1 is s2
    with s1 as sp:
        assert sp.sync(123) == 123
        sp.annotate(ignored=True)
    assert sp.duration_s == 0.0
    assert rec.events() == []


def test_timed_span_measures_while_disabled():
    """timed=True callers (engine latency accounting, benchmarks) get real
    wall time regardless of tracing — but still record nothing."""
    rec = trace.get_recorder()
    assert not rec.enabled
    with trace.span("work", phase="query", timed=True) as sp:
        sum(range(1000))
    assert sp.duration_s > 0.0
    assert rec.events() == []


def test_span_nesting_depth_and_phase_inheritance(recorder):
    with trace.span("outer", phase="build"):
        with trace.span("inner"):          # no phase -> inherits "build"
            with trace.span("leaf", phase="query"):
                pass
    evs = {e["name"]: e for e in recorder.events()}
    assert evs["outer"]["depth"] == 0 and evs["outer"]["phase"] == "build"
    assert evs["inner"]["depth"] == 1 and evs["inner"]["phase"] == "build"
    assert evs["leaf"]["depth"] == 2 and evs["leaf"]["phase"] == "query"
    # children complete before parents; timestamps nest inside the parent
    names = [e["name"] for e in recorder.events()]
    assert names == ["leaf", "inner", "outer"]
    assert evs["outer"]["ts_s"] <= evs["inner"]["ts_s"]
    assert (evs["inner"]["ts_s"] + evs["inner"]["dur_s"]
            <= evs["outer"]["ts_s"] + evs["outer"]["dur_s"] + 1e-9)


def test_span_sync_blocks_jax_outputs(recorder):
    jnp = pytest.importorskip("jax.numpy")
    with trace.span("device_work", phase="build") as sp:
        out = sp.sync(jnp.arange(512) * 2)
    assert int(np.asarray(out)[-1]) == 1022
    (ev,) = recorder.events()
    assert ev["name"] == "device_work" and ev["dur_s"] > 0


def test_top_level_seconds_counts_only_depth_zero(recorder):
    with trace.span("a", phase="build"):
        with trace.span("a.child"):
            pass
    with trace.span("b", phase="query"):
        pass
    evs = recorder.events()
    expect = sum(e["dur_s"] for e in evs if e["depth"] == 0)
    assert recorder.top_level_seconds() == pytest.approx(expect)
    assert recorder.phases_seen() == {"build", "query"}


def test_chrome_trace_schema_and_lanes(recorder):
    """The export is valid Chrome trace-event JSON (what Perfetto loads):
    a traceEvents list of M metadata + X complete events, one tid lane per
    phase, ts/dur in microseconds."""
    with trace.span("plan_it", phase="plan", n=64):
        pass
    with trace.span("query_it", phase="query"):
        pass
    doc = json.loads(json.dumps(recorder.chrome_trace()))   # JSON-clean
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    assert all(e["ph"] in ("M", "X") for e in events)
    names = {e["args"]["name"] for e in events
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert names == {"plan", "query"}
    xs = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"plan_it", "query_it"}
    by_name = {e["name"]: e for e in xs}
    assert by_name["plan_it"]["tid"] == trace.PHASES.index("plan")
    assert by_name["query_it"]["tid"] == trace.PHASES.index("query")
    assert by_name["plan_it"]["args"]["n"] == 64
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0 and e["cat"] in trace.PHASES


def test_save_chrome_trace_roundtrip(tmp_path, recorder):
    with trace.span("one", phase="build"):
        pass
    path = tmp_path / "trace.json"
    n = recorder.save_chrome_trace(str(path))
    assert n == 1
    doc = json.loads(path.read_text())
    assert any(e["ph"] == "X" and e["name"] == "one"
               for e in doc["traceEvents"])


def test_traced_decorator(recorder):
    @trace.traced("deco.region", phase="repair")
    def work(a, b):
        return a + b

    assert work(2, 3) == 5
    (ev,) = recorder.events()
    assert ev["name"] == "deco.region" and ev["phase"] == "repair"


def test_span_parent_links(recorder):
    """Each event names the span that encloses it, so self time is its
    duration less its children's."""
    with trace.span("job"):
        with trace.span("prep"):
            with trace.span("sort"):
                pass
        with trace.span("run"):
            pass
    with trace.span("next"):
        pass
    evs = {e["name"]: e for e in recorder.events()}
    assert len({e["id"] for e in evs.values()}) == 5
    assert evs["job"]["parent"] is None and evs["next"]["parent"] is None
    assert evs["prep"]["parent"] == evs["job"]["id"]
    assert evs["run"]["parent"] == evs["job"]["id"]
    assert evs["sort"]["parent"] == evs["prep"]["id"]
    children = sum(e["dur_s"] for e in evs.values()
                   if e["parent"] == evs["job"]["id"])
    assert 0 <= evs["job"]["dur_s"] - children <= evs["job"]["dur_s"]


def test_disabled_span_makes_no_profiler_call(monkeypatch):
    """The recorder off: the shared null span, and no TraceAnnotation; on:
    one annotation per span, entered and left around it."""
    jax = pytest.importorskip("jax")
    calls = []

    class Annotation:
        def __init__(self, name):
            calls.append(("new", name))

        def __enter__(self):
            calls.append(("enter",))

        def __exit__(self, *exc):
            calls.append(("exit",))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    assert trace.span("a") is trace.span("b")
    with trace.span("off", phase="build"):
        pass
    assert calls == []
    rec = trace.get_recorder()
    rec.start()
    try:
        with trace.span("on", phase="build"):
            assert calls == [("new", "on"), ("enter",)]
    finally:
        rec.stop()
        rec.clear()
    assert calls[-1] == ("exit",)


def test_span_lands_on_the_profiler_host_plane(tmp_path, recorder):
    """A recorded span is a host event of a profiler trace taken meanwhile,
    on the device trace's clock."""
    import glob

    jax = pytest.importorskip("jax")
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("obs.profiled", phase="build") as sp:
            sp.sync(jax.numpy.arange(64) + 1)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert "obs.profiled" in names


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def test_counter_gauge_get_or_create():
    reg = metrics.MetricsRegistry()
    c = reg.counter("hits", path="warm")
    c.inc()
    c.inc(4)
    assert reg.counter("hits", path="warm") is c and c.value == 5
    assert reg.counter("hits", path="cold") is not c
    g = reg.gauge("resident")
    g.set(3)
    assert reg.gauge("resident").value == 3.0
    with pytest.raises(TypeError):
        reg.gauge("hits", path="warm")    # kind mismatch on same name+tags


def test_histogram_percentiles_match_numpy():
    """Streaming (geometric-bucket) percentiles land within the bucket
    resolution (~2% relative) of numpy's exact order statistics."""
    rng = np.random.default_rng(7)
    samples = rng.lognormal(mean=-3.0, sigma=1.2, size=20_000)
    h = metrics.Histogram(unit="s")
    for v in samples:
        h.observe(v)
    for q in (50, 95, 99):
        exact = float(np.percentile(samples, q))
        assert h.percentile(q) == pytest.approx(exact, rel=0.05), q
    assert h.count == len(samples)
    assert h.min == pytest.approx(samples.min())
    assert h.max == pytest.approx(samples.max())
    assert h.mean == pytest.approx(samples.mean(), rel=1e-6)
    assert h.percentile(0) == h.min and h.percentile(100) == h.max


def test_histogram_edge_cases():
    h = metrics.Histogram()
    assert h.percentile(50) == 0.0          # empty
    h.observe(0.0)
    h.observe(-1.0)                          # underflow bucket
    h.observe(2.5)
    s = h.summary()
    assert s["count"] == 3 and s["min"] == -1.0 and s["max"] == 2.5
    assert 0.0 <= s["p50"] <= 2.5


def test_snapshot_jsonl_roundtrip(tmp_path):
    reg = metrics.MetricsRegistry()
    reg.counter("events", kind="delta").inc(3)
    reg.gauge("frac").set(0.25)
    reg.histogram("lat", unit="s").observe(0.01)
    path = tmp_path / "metrics.jsonl"
    assert reg.write_jsonl(str(path)) == 3
    rows = metrics.load_jsonl(str(path))
    by_name = {(r["name"], tuple(sorted(r["tags"].items()))): r for r in rows}
    assert by_name[("events", (("kind", "delta"),))]["value"] == 3
    assert by_name[("frac", ())]["value"] == 0.25
    lat = by_name[("lat", ())]
    assert lat["kind"] == "histogram" and lat["unit"] == "s"
    assert lat["count"] == 1 and lat["p99"] > 0


# ---------------------------------------------------------------------------
# end-to-end: the serve driver under --trace/--metrics
# ---------------------------------------------------------------------------


def test_serve_im_trace_covers_build_and_query(tmp_path):
    """Smoke the serving driver with tracing on: the written artifact is
    Perfetto-loadable Chrome JSON whose lanes cover the build and query
    phases of the run."""
    from repro.launch.serve_im import run

    trace_path = tmp_path / "serve_trace.json"
    metrics_path = tmp_path / "serve_metrics.jsonl"
    try:
        out = run(["--graph", "rmat:7", "--registers", "64", "--queries",
                   "20", "--topk", "4", "--trace", str(trace_path),
                   "--metrics", str(metrics_path)])
    finally:
        rec = trace.get_recorder()
        rec.stop()
        rec.clear()
    assert out["num_queries"] == 20
    doc = json.loads(trace_path.read_text())
    lanes = {e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"build", "query"} <= lanes, lanes
    assert any(e["ph"] == "X" for e in doc["traceEvents"])
    rows = metrics.load_jsonl(str(metrics_path))
    names = {r["name"] for r in rows}
    assert "store.bank_build_s" in names
    assert "engine.requests" in names
