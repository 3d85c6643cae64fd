"""The trace reduction on small traces: a hand-made two-chip trace whose
every number is known, and a trace recorded here on the CPU."""
import glob

import jax
import pytest
from jax.profiler import ProfileData

from bench import tracereduce as tr

US = 1000  # ns


def _xspace(planes):
    """Text proto of an XSpace: planes = {name: {line: [(name, t0, t1)]}},
    times in microseconds."""
    meta, out = {}, []
    for pid, (pname, lines) in enumerate(planes.items(), start=1):
        body = []
        for lid, (lname, events) in enumerate(lines.items(), start=1):
            evs = []
            for name, t0, t1 in events:
                mid = meta.setdefault((pid, name), len(meta) + 1)
                evs.append(f"events {{ metadata_id: {mid} offset_ps: "
                           f"{t0 * US * 1000} duration_ps: "
                           f"{(t1 - t0) * US * 1000} }}")
            body.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0 '
                        + " ".join(evs) + " }")
        for (p, name), mid in meta.items():
            if p == pid:
                body.append(f'event_metadata {{ key: {mid} value {{ id: {mid} '
                            f'name: "{name}" }} }}')
        out.append(f'planes {{ id: {pid} name: "{pname}" ' + " ".join(body)
                   + " }")
    return "\n".join(out)


@pytest.fixture(scope="module")
def two_chips():
    text = _xspace({
        "/device:TPU:0": {
            "XLA Ops": [("fusion.1", 0, 10), ("while.1", 10, 40),
                        ("fusion.2", 12, 20), ("fusion.3", 25, 35),
                        ("collective-permute-done.1", 40, 50),
                        ("all-reduce.1", 50, 55), ("fusion.4", 55, 60)],
            "Steps": [("step 0", 0, 60)]},
        "/device:TPU:1": {"XLA Ops": [("fusion.1", 0, 30)]},
        "/host:CPU": {"python": [("bench.job", 0, 100),
                                 ("PjitFunction(f)", 5, 95),
                                 ("np.sort", 75, 85)]},
    })
    data = tr.from_profile(ProfileData.from_text_proto(text))
    return tr.Reduction(data, window=(0, 100 * US))


def test_planes_split_into_device_ops_and_host_events(two_chips):
    data = two_chips.data
    assert sorted(data.devices) == ["/device:TPU:0", "/device:TPU:1"]
    assert len(data.devices["/device:TPU:0"]) == 7   # the Steps line is not ops
    assert {e.name for e in data.host} == {"bench.job", "PjitFunction(f)",
                                           "np.sort"}


def test_busy_and_idle_share(two_chips):
    # chip 0 busy 0-60 us, chip 1 busy 0-30 us: mean 45 us of 100
    assert two_chips.window_s == pytest.approx(100e-6)
    assert two_chips.busy_s() == pytest.approx(45e-6)
    assert two_chips.idle_share() == pytest.approx(0.55)
    assert two_chips.busy_s(0, 20 * US) == pytest.approx(20e-6)


def test_collective_exposure(two_chips):
    # chip 0 waits on collectives from 40 to 55 us, chip 1 never
    assert two_chips.collective_exposed_share() == pytest.approx(0.075)


def test_op_self_times_do_not_count_a_loop_body_twice(two_chips):
    top = dict(two_chips.top_ops())
    assert top["while.1"] == pytest.approx(12e-6 / 2)
    assert top["fusion.1"] == pytest.approx(40e-6 / 2)
    assert sum(top.values()) == pytest.approx(two_chips.busy_s())


def test_idle_gap_named_by_innermost_host_event(two_chips):
    gaps = two_chips.idle_gaps()
    assert gaps[0][0] == "bench.job > np.sort"
    assert gaps[0][1] == pytest.approx(40e-6)


def test_annotation_and_no_collectives():
    text = _xspace({"/device:TPU:0": {"XLA Ops": [("fusion.1", 0, 10)]},
                    "/host:CPU": {"main": [("bench.build", 2, 8)]}})
    red = tr.Reduction(tr.from_profile(ProfileData.from_text_proto(text)))
    assert red.data.annotation("bench.build") == (2 * US, 8 * US)
    assert red.data.annotation("bench.job") is None
    assert red.collective_exposed_share() is None
    assert red.idle_share() == pytest.approx(0.0)


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(2, 3), (5, 12)], [(0, 2), (3, 5)]),
    ([(0, 10), (20, 30)], [], [(0, 10), (20, 30)]),
    ([(0, 10)], [(-5, 20)], []),
    ([(0, 5), (8, 12)], [(4, 9)], [(0, 4), (9, 12)]),
])
def test_interval_subtract(a, b, want):
    assert tr.subtract(a, b) == want


def test_merge_and_clip():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert tr.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]


def test_trace_recorded_on_cpu(tmp_path):
    f = jax.jit(lambda a: (a @ a).sum())
    a = jax.numpy.ones((64, 64))
    f(a).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.job"):
        f(a).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    data = tr.load(path)
    assert data.devices == {}          # the CPU backend has no device plane
    red = tr.Reduction(data, window=(0.0, 1.0))
    assert data.annotation("bench.job") is not None
    assert red.busy_s() == 0.0 and red.idle_share() is None


@pytest.mark.parametrize("hlo,label,collective", [
    ("%fusion.73 = u8[4104,1024]{1,0:T(8,128)(4,1)S(1)} fusion(u8[4104,1024]"
     "{1,0:T(8,128)(4,1)S(1)} %get-tuple-element.1072), kind=kCustom, "
     "calls=%fused_computation.25", "fusion.73 u8[4104,1024] fusion kCustom",
     False),
    ("%sort.7 = (s32[2048]{0:T(1024)S(1)}, s32[2048]{0:T(1024)S(1)}) "
     "sort(s32[2048]{0:T(1024)S(1)} %bitcast.87), dimensions={0}",
     "sort.7 tuple sort", False),
    ("%collective-permute-start.1 = (s8[2,3]{1,0}, s8[2,3]{1,0}) "
     "collective-permute-start(s8[2,3]{1,0} %p)",
     "collective-permute-start.1 tuple collective-permute-start", True),
    ("%all-reduce.3 = f32[2,8]{1,0:T(2,128)} all-reduce(f32[2,8]{1,0} %x)",
     "all-reduce.3 f32[2,8] all-reduce", True),
    ("%fusion.9 = s8[8]{0} fusion(s8[8]{0} %all-reduce.3), kind=kLoop",
     "fusion.9 s8[8] fusion kLoop", False),
    ("collective-permute-done.1", "collective-permute-done.1", True),
])
def test_op_labels_and_collectives(hlo, label, collective):
    assert tr.op_label(hlo) == label
    assert tr.is_collective(tr.op_label(hlo)) is collective


def test_trace_recorded_on_a_v5e():
    """One build and one job at scale 8, traced on a TPU v5e chip."""
    import gzip
    from pathlib import Path

    raw = (Path(__file__).parent / "testdata" /
           "v5e_scale8_job.xplane.pb.gz").read_bytes()
    data = tr.from_profile(ProfileData.from_serialized_xspace(
        gzip.decompress(raw)))
    assert list(data.devices) == ["/device:TPU:0"]
    assert len(data.devices["/device:TPU:0"]) == 3517
    build, job = data.annotation("bench.build"), data.annotation("bench.job")
    assert build[1] <= job[0]
    red = tr.Reduction(data, window=(build[0], job[1]))
    assert red.window_s == pytest.approx(0.02459516)
    assert red.busy_s() == pytest.approx(0.01470146)
    assert red.busy_s(*build) == pytest.approx(0.00179811)
    assert red.collective_exposed_share() is None
    top = red.top_ops()
    assert top[0] == ("fusion.37 s8[264,1024] fusion kCustom",
                      pytest.approx(0.004498938))
    assert sum(t for _, t in red.top_ops(10**6)) == pytest.approx(red.busy_s())
    assert red.idle_gaps(1)[0] == ("bench.job > shard_args",
                                   pytest.approx(0.003735511))
