"""The fused epoch's anatomy as the benchmark reads it (PR 34): the two
source kinds on hand-made inputs — ``program_span_per_epoch`` on
intervals put into the program's own registry, ``trace_idle_inside_spans``
on a small scoped xplane beside its complement — the four metrics'
files against BENCHMARK.json, and the traced tiny fused run, whose line
carries the three that need no device plane."""
import json
import os

import pytest

import bench_tiny
import test_bench_run
from benchmarks import harness
from benchmarks.reduce import xplane as X
from benchmarks.sources import (program_span_per_epoch,
                                trace_idle_inside_spans,
                                trace_idle_outside_spans)
from ddls_tpu import telemetry
from test_bench_scopes import write_scoped_xplane

tiny_tree = test_bench_run.tiny_tree
restore_process_state = test_bench_run.restore_process_state

CELL = "mimo_ramp32.train_fused"
SPAN_METRICS = ("epoch_device_wait_p50_s", "epoch_host_p50_ms",
                "epoch_observer_p50_ms")
NEW_METRICS = (*SPAN_METRICS, "device_idle_observer_share")
BENCH = json.load(open(os.path.join(bench_tiny.REPO, "BENCHMARK.json")))


@pytest.fixture()
def registry():
    """The program's global registry, recording intervals, emptied
    before and after."""
    telemetry.disable()
    telemetry.reset()
    telemetry.enable(record_intervals=True)
    yield telemetry
    telemetry.disable()
    telemetry.enable(record_intervals=False)
    telemetry.disable()
    telemetry.reset()


def _source(*names, **kw):
    return {"kind": "program_span_per_epoch", "names": list(names), **kw}


# ------------------------------------------------ program_span_per_epoch
def test_spans_are_summed_inside_each_epoch_then_reduced(registry):
    """Three epochs, delimited by successive ``train.fused_epoch``
    starts; the last runs to the end of the record. A span belongs to
    the epoch it STARTS in; spans before the first marker belong to
    none; the ring is read after ``disable()``."""
    spans = [("train.host_sync", 0.5, 0.9),            # before any epoch
             ("train.fused_epoch", 1.0, 1.1),
             ("train.device_wait", 1.1, 1.6),
             ("train.host_sync", 1.6, 1.7),
             ("train.host_sync", 1.7, 1.75),           # two an epoch
             ("train.fused_epoch", 2.0, 2.2),
             ("train.device_wait", 2.2, 2.5),
             ("train.host_sync", 2.5, 2.9),
             ("train.fused_epoch", 3.0, 3.05),
             ("train.device_wait", 3.05, 3.95),
             ("train.host_sync", 3.95, 4.2)]           # runs past 4.0
    for name, t0, t1 in spans:
        registry.record_span(name, t0, t1)
    registry.disable()
    read = program_span_per_epoch.read
    assert telemetry.per_epoch_sums(
        registry.span_intervals(), {"train.device_wait"}) == \
        pytest.approx([0.5, 0.3, 0.9])
    assert read(_source("train.device_wait"), {}) == pytest.approx(0.5)
    assert read(_source("train.device_wait", stat="max"), {}) == \
        pytest.approx(0.9)
    assert read(_source("train.host_sync"), {}) == pytest.approx(0.25)
    # the sum of several names, per epoch: 0.25 / 0.6 / 0.3
    assert read(_source("train.fused_epoch", "train.host_sync"), {}) == \
        pytest.approx(0.3)
    assert read(_source("train.fused_epoch", "train.host_sync",
                        stat="sum"), {}) == pytest.approx(1.15)


def test_an_older_program_gives_nothing_to_read(registry):
    """No marker span, or a named span the program never wrote: None
    (the harness leaves the metric out), never 0 and never a raise."""
    read = program_span_per_epoch.read
    assert read(_source("train.device_wait"), {}) is None
    registry.record_span("train.host_sync", 0.0, 1.0)
    assert read(_source("train.host_sync"), {}) is None     # no marker
    registry.record_span("train.fused_epoch", 2.0, 2.1)
    registry.record_span("train.host_sync", 2.1, 2.4)
    assert read(_source("train.host_sync"), {}) == pytest.approx(0.3)
    # the parent's program: the marker, but no train.device_wait; and a
    # sum is not read from the part of its names that exists
    assert read(_source("train.device_wait"), {}) is None
    assert read(_source("train.host_sync", "train.harvest"), {}) is None
    for name in SPAN_METRICS:
        assert harness.read_layer_metric(name, {}) is None


# ----------------------------------------------- trace_idle_inside_spans
DDLS_SPANS = [("ddls.train.fused_epoch", 900, 1100),
              ("ddls.train.device_wait", 1100, 4050),
              ("ddls.train.host_sync", 4050, 4400),
              ("ddls.train.telemetry_reduce", 4400, 4700),
              ("ddls.train.harvest", 4700, 4800)]


@pytest.fixture()
def idle_ctx(tmp_path):
    """One device, busy 1000-2000 and 3000-4000 of a 0-6000 window (4000
    idle), under the five spans of an epoch: 100 idle before the
    program starts inside the dispatch, 1000 + 50 inside the wait (the
    gap between the two programs and a tail), 350 / 300 / 100 inside
    the copies, the reducers and the harvest, 2100 inside no span."""
    path = str(tmp_path / "host.xplane.pb")
    write_scoped_xplane(
        path,
        [("%fusion.1 = f32[] fusion(a)", None, 1000, 2000),
         ("%fusion.1 = f32[] fusion(a)", None, 3000, 4000)],
        [("jit_epoch(77)", 1000, 2000), ("jit_epoch(77)", 3000, 4000)],
        host_spans=[("bench.trace_window", 0, 6000), *DDLS_SPANS])
    return {"trace": X.Trace.from_file(path)}


def _inside(*names):
    return {"kind": "trace_idle_inside_spans", "names": list(names)}


def test_idle_inside_named_spans_and_outside_all_sum_to_one(idle_ctx):
    read = trace_idle_inside_spans.read
    assert read(_inside("ddls.train.telemetry_reduce"), idle_ctx) == \
        pytest.approx(300 / 4000)
    assert read(_inside("ddls.train.device_wait"), idle_ctx) == \
        pytest.approx(1050 / 4000)
    assert read(_inside("ddls.train.host_sync", "ddls.train.harvest"),
                idle_ctx) == pytest.approx(450 / 4000)
    every = read(_inside(*(name for name, _, _ in DDLS_SPANS)), idle_ctx)
    outside = trace_idle_outside_spans.read(
        {"kind": "trace_idle_outside_spans", "prefix": "ddls."}, idle_ctx)
    assert outside == pytest.approx(2100 / 4000)
    assert every + outside == pytest.approx(1.0)
    # the metric, through its file, in per cent
    assert harness.read_layer_metric(
        "device_idle_observer_share", idle_ctx) == pytest.approx(7.5)


def test_idle_inside_spans_reads_nothing_without_them(idle_ctx, tmp_path):
    read = trace_idle_inside_spans.read
    # a name no span carries (the parent's trace): nothing, not 0
    assert read(_inside("ddls.train.no_such_span"), idle_ctx) is None
    # a prefix of a name is not the name
    assert read(_inside("ddls.train.telemetry"), idle_ctx) is None
    assert read(_inside("ddls.train.harvest"), {"trace": None}) is None
    # a device that was never idle
    path = str(tmp_path / "busy.xplane.pb")
    write_scoped_xplane(
        path, [("%fusion.1 = f32[] fusion(a)", None, 0, 6000)],
        [("jit_epoch(77)", 0, 6000)],
        host_spans=[("bench.trace_window", 0, 6000), *DDLS_SPANS])
    assert read(_inside("ddls.train.harvest"),
                {"trace": X.Trace.from_file(path)}) is None


# ------------------------------------------------------ the four metrics
@pytest.mark.parametrize("metric", NEW_METRICS)
def test_metric_is_listed_for_mimo_and_agrees_with_its_file(metric):
    spec = harness.read_json(os.path.join(
        harness.BENCH_DIR, "layer_metrics", metric + ".json"))
    entry, = [m for m in BENCH["per_layer"] if m["name"] == metric]
    assert CELL in entry["workloads"]
    assert (entry["layer"], entry["unit"], entry["moves"]) \
        == (spec["layer"], spec["unit"], spec["moves"])
    assert entry["better"] == "lower"
    assert spec["moves"] == "train_env_steps_per_s" in {
        m["name"] for m in harness.load_cell(CELL).end_to_end}
    assert metric in {m["name"] for m in harness.load_cell(CELL).per_layer}
    if metric in SPAN_METRICS:
        assert (entry["source"], spec["layer"], spec["source"]["kind"]) \
            == ("program_span", "epoch loop", "program_span_per_epoch")
        assert set(spec["source"]["names"]) <= {
            "train.fused_epoch", "train.device_wait", "train.host_sync",
            "train.harvest", "train.telemetry_reduce"}
        assert spec["scale"] == (1000 if spec["unit"] == "ms" else 1)
    else:
        assert (entry["source"], spec["layer"], spec["source"]) == (
            "device_trace", "device",
            {"kind": "trace_idle_inside_spans",
             "names": ["ddls.train.telemetry_reduce"]})


def test_the_four_displaced_nothing_the_old_cells_reported():
    """The four were appended: none stands among the parent's 36 entries
    nor among the 34 names that each of the four older cells reported
    (found by name: where later entries stand, and which other cells
    come to list the four, is a later PR's to change)."""
    new = set(NEW_METRICS)
    assert not new & {m["name"] for m in BENCH["per_layer"][:36]}
    for cell in ("ramp32_dev", "ramp32_load32", "olmoe_ramp32",
                 "glm5_ramp32"):
        names = [m["name"] for m in harness.load_cell(
            cell + ".train_fused").per_layer]
        assert len(names) >= 34 and not new & set(names[:34])


# ------------------------------------------------ the tiny fused run
def test_traced_tiny_fused_line_carries_the_epochs_anatomy(tiny_tree,
                                                           capsys):
    """Every epoch of the window leaves its spans: the device wait, the
    host's time and the observer's, each a positive number under the
    epoch's own wall, and together the epoch the benchmark wraps from
    outside, to within what lies between the spans. No device plane on
    the CPU: the trace's share is left out, not faked."""
    result, _ = test_bench_run._result(
        capsys, test_bench_run._argv("tiny.fused", 1))
    test_bench_run._check_line(result, traced=True)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(SPAN_METRICS) <= set(metrics)
    assert "device_idle_observer_share" not in metrics
    wall = metrics["epoch_wall_p50_s"]
    parts = (metrics["epoch_device_wait_p50_s"]
             + (metrics["epoch_host_p50_ms"]
                + metrics["epoch_observer_p50_ms"]) / 1e3)
    assert all(metrics[name] > 0 for name in SPAN_METRICS)
    assert 0.7 * wall < parts < 1.05 * wall
