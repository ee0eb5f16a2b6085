"""The trace reduction: interval arithmetic on hand-made cases, small
xplanes written through the real file format, and the recorded v5e
trace of two host-collected epochs under ``benchmarks/testdata/``."""
import os

import pytest

import bench_tiny
from benchmarks import harness
from benchmarks.reduce import xplane as X

RECORDED = os.path.join(harness.BENCH_DIR, "testdata",
                        "train_host_v5e.xplane.pb.gz")


# ------------------------------------------------------------ intervals
@pytest.mark.parametrize("given, want", [
    ([], []),
    ([(0, 5), (3, 8)], [(0, 8)]),                      # overlap
    ([(0, 10), (2, 3), (4, 5)], [(0, 10)]),            # nesting
    ([(0, 1), (1, 2)], [(0, 2)]),                      # touching
    ([(5, 6), (0, 1)], [(0, 1), (5, 6)]),              # unsorted
    ([(3, 3), (4, 2)], []),                            # empty, inverted
])
def test_union(given, want):
    assert X.union(given) == want


def test_total_clip_subtract_and_gaps():
    assert X.total([(0, 2), (5, 6)]) == 3
    assert X.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
    assert X.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                       (7, 10)]
    assert X.subtract([(0, 10)], [(-5, 20)]) == []
    assert X.subtract([(0, 4), (6, 8)], []) == [(0, 4), (6, 8)]
    assert X.gaps([(2, 3)], 0, 5) == [(0, 2), (3, 5)]
    assert X.gaps([], 0, 5) == [(0, 5)]
    assert X.gaps([(0, 5)], 0, 5) == []


def test_short_op_name():
    assert X.short_op_name(
        "%fusion.12 = f32[8,128]{1,0} fusion(f32[8] %p)") == "fusion.12"
    assert X.short_op_name("plain") == "plain"


# ------------------------------------------------------- small xplanes
def _trace(tmp_path, planes):
    path = str(tmp_path / "small.xplane.pb")
    X.write_xplane(planes, path)
    return X.Trace.from_file(path)


def _device(n, ops, modules=()):
    return X.Plane(f"/device:TPU:{n}", [
        X.Line("XLA Ops", [X.Event(*e) for e in ops]),
        X.Line("XLA Modules", [X.Event(*e) for e in modules])])


def _host(spans):
    return X.Plane("/host:CPU", [X.Line("main/1",
                                        [X.Event(*s) for s in spans])])


def test_nested_ops_are_unioned_not_summed(tmp_path):
    trace = _trace(tmp_path, [
        _device(0, [("%while.1 = () while(x)", 100, 900),
                    ("%fusion.2 = f32[] fusion(a)", 150, 300),
                    ("%fusion.2 = f32[] fusion(a)", 400, 500),
                    ("%copy.3 = f32[] copy(a)", 1000, 1100)],
                [("jit_step(11)", 90, 950), ("jit_step(11)", 990, 1150),
                 ("jit_other(5)", 1200, 1210)]),
        _host([("bench.trace_window", 0, 2000), ("bench.epoch", 50, 1500),
               ("bench.collect", 920, 990), ("other", 0, 5)])])
    assert trace.window == (0, 2000)
    assert trace.window_s == pytest.approx(2000e-9)
    assert trace.busy_s() == pytest.approx(900e-9)        # 800 + 100
    assert trace.idle_share_per_device() == [pytest.approx(0.55)]
    assert trace.program_durations_s(r"^jit_step\(") == [
        [pytest.approx(860e-9), pytest.approx(160e-9)]]
    assert trace.program_names() == {
        "jit_step": pytest.approx(1020e-9),
        "jit_other": pytest.approx(10e-9)}
    assert trace.top_ops(2) == [("while.1", pytest.approx(800e-9)),
                                ("fusion.2", pytest.approx(250e-9))]
    # the gaps: [1100, 2000] (mid 1550: nothing open), [0, 100]
    # (mid 50: epoch just opened), [900, 1000] (mid 950: collect inside
    # epoch -> the innermost wins)
    assert trace.idle_gaps(3) == [
        ("unattributed", pytest.approx(900e-9)),
        ("epoch", pytest.approx(100e-9)),
        ("collect", pytest.approx(100e-9))]
    assert trace.idle_by_span() == {
        "unattributed": pytest.approx(900e-9),
        "epoch": pytest.approx(100e-9),
        "collect": pytest.approx(100e-9)}


def test_events_outside_the_window_do_not_count(tmp_path):
    trace = _trace(tmp_path, [
        _device(0, [("%a = f32[] add(x)", 0, 100),
                    ("%b = f32[] add(x)", 150, 400)]),
        _host([("bench.trace_window", 50, 250)])])
    assert trace.busy_s() == pytest.approx(150e-9)   # 50..100 + 150..250
    assert trace.idle_share_per_device() == [pytest.approx(0.25)]


def test_window_falls_back_to_the_device_events(tmp_path):
    trace = _trace(tmp_path, [_device(0, [("%a = f32[] add(x)", 10, 20),
                                          ("%b = f32[] add(x)", 30, 50)])])
    assert trace.window == (10, 50)
    assert trace.idle_share_per_device() == [pytest.approx(0.25)]


def test_empty_planes_give_nothing_to_read(tmp_path):
    trace = _trace(tmp_path, [X.Plane("/device:TPU:0", []),
                              X.Plane("/host:CPU", [])])
    assert trace.window is None and trace.window_s == 0.0
    assert trace.busy_s() is None
    assert trace.idle_share_per_device() == []
    assert trace.top_ops() == [] and trace.idle_gaps() == []
    assert trace.program_durations_s("jit") == [[]]
    no_device = _trace(tmp_path, [_host([("bench.trace_window", 0, 10)])])
    assert no_device.busy_s() is None


def test_exposed_collective_time_per_device(tmp_path):
    ops0 = [("%while.9 = () while(x)", 0, 1000),         # a container
            ("%fusion.1 = f32[] fusion(a)", 0, 400),
            ("%all-reduce.2 = f32[] all-reduce(a)", 300, 600),
            ("%fusion.3 = f32[] fusion(a)", 700, 900)]
    ops1 = [("%fusion.1 = f32[] fusion(a)", 0, 1000),
            ("%all-reduce.2 = f32[] all-reduce(a)", 300, 600)]
    trace = _trace(tmp_path, [_device(0, ops0), _device(1, ops1),
                              _host([("bench.trace_window", 0, 1000)])])
    assert [p.name for p in trace.devices] == ["/device:TPU:0",
                                               "/device:TPU:1"]
    # device 0: the all-reduce runs alone from 400 to 600; device 1:
    # always under a fusion
    assert trace.collective_exposed_s_per_device() == [
        pytest.approx(200e-9), pytest.approx(0.0)]
    assert trace.busy_s() == pytest.approx(1000e-9)


# ------------------------------------------------- the recorded trace
@pytest.fixture(scope="module")
def recorded():
    return X.Trace.from_file(RECORDED)


def test_recorded_trace_planes_and_lines(recorded):
    assert os.path.getsize(RECORDED) < 1_000_000
    assert [p.name for p in recorded.devices] == ["/device:TPU:0"]
    assert recorded.hosts and recorded.hosts[0].name == "/host:CPU"
    device = recorded.devices[0]
    assert device.line("XLA Ops").events
    assert device.line("XLA Modules").events
    spans = {e.name for e in recorded.host_spans()}
    assert {"bench.trace_window", "bench.epoch", "bench.collect",
            "bench.update_dispatch", "bench.host_sync"} <= spans


def test_recorded_trace_busy_idle_and_programs(recorded):
    """Three host-collected epochs on one v5e (my chip run, PR 22); the
    fixture keeps every program execution and the operations of the
    first 1.5 s, and its window span is cut to that slice."""
    assert recorded.window_s == pytest.approx(1.5)
    assert len(recorded.devices[0].line("XLA Ops").events) == 133_517
    assert recorded.busy_s() == pytest.approx(0.621550048, rel=1e-9)
    assert recorded.idle_share_per_device() == [
        pytest.approx(0.5856333, rel=1e-6)]
    # the update program ran once per epoch, 0.386 s each time; the
    # sampling program 33 times per epoch at 0.149 ms
    updates = recorded.program_durations_s(r"^jit__train_step\(")[0]
    assert updates == [pytest.approx(x, rel=1e-9) for x in (
        0.385940488, 0.385867943, 0.385930086)]
    samples = recorded.program_durations_s(r"^jit_step_fn\(")[0]
    assert len(samples) == 99
    assert sorted(samples)[49] == pytest.approx(0.000149142, rel=1e-9)
    assert recorded.program_names()["jit__train_step"] == pytest.approx(
        1.157738517, rel=1e-9)
    # nested: the update is one while (the scan over minibatch steps)
    # holding another; their union, not their sum, is the busy time
    top = recorded.top_ops(2)
    assert [name for name, _ in top] == ["while.208", "while.210"]
    assert top[0][1] > recorded.busy_s()   # summed over two executions


def test_recorded_trace_gaps_are_named_by_the_host_span(recorded):
    gaps = recorded.idle_gaps(5)
    assert gaps[0] == ("collect", pytest.approx(0.053621553, rel=1e-9))
    assert {name for name, _ in gaps} == {"collect"}
    by_span = recorded.idle_by_span()
    # the chip idles while the host steps the env workers
    assert by_span["collect"] == pytest.approx(0.851051183, rel=1e-6)
    assert by_span["collect"] > 0.95 * sum(by_span.values())
    assert sum(by_span.values()) == pytest.approx(
        recorded.window_s - recorded.busy_s(), rel=1e-6)
    assert recorded.collective_exposed_s_per_device() == [0.0]


def test_readers_on_the_recorded_trace(recorded):
    """The per-layer readers of the host-collected cell against the
    fixture, through the metric files as the harness reads them."""
    cell = bench_tiny.unlisted_cell("pacml_ramp32_dev", "train_host_8x32")
    ctx = {"trace": recorded, "cell": cell,
           "device": {"kind": "TPU v5 lite"}}
    read = harness.read_layer_metric
    assert read("update_device_s", ctx) == pytest.approx(0.385930086)
    assert read("sample_forward_device_ms", ctx) == pytest.approx(0.149142)
    assert read("device_idle_share", ctx) == pytest.approx(58.56333, rel=1e-6)
    # 20.4 ms of HBM traffic at 819 GB/s over 386 ms: memory side
    assert read("update_roofline", ctx) == pytest.approx(5.289, rel=1e-3)
    assert read("fused_epoch_device_s", ctx) is None   # no such program
    assert read("collective_exposed_share", ctx) is None   # one chip
    assert read("update_device_s", {"trace": None}) is None


def test_roofline_says_which_bound_applies():
    from benchmarks.sources import roofline

    cell = bench_tiny.unlisted_cell("pacml_ramp32_dev", "train_host_8x32")
    flops, nbytes, seconds, side = roofline.bound(
        {"shape_fn": "ppo_update"},
        {"cell": cell, "device": {"kind": "TPU v5 lite"}})
    assert flops == pytest.approx(1.455e11, rel=1e-3)   # 100 steps
    assert flops / 100 == pytest.approx(0.77 * 1.88e9, rel=0.02)
    assert nbytes == pytest.approx(1.672e10, rel=1e-3)
    assert side == "memory" and seconds == pytest.approx(0.02041, rel=1e-3)
