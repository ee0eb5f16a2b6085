"""``lookahead_narrow_trip_share`` (PR 40): the metric is DATA of reader
kinds the benchmark already has (a ratio of two of the program's
telemetry counters, both per epoch, so the window's epochs cancel), it
is listed for every cell behind what was there, a program without the
counter reads nothing and does not raise, and a tiny fused run traced on
the CPU reports it beside the counters it is made of."""
import json
import os

import pytest

import bench_tiny
import test_bench_run
from benchmarks import harness
from test_bench_run import restore_process_state, tiny_tree  # noqa: F401

METRIC = "lookahead_narrow_trip_share"
PARTS = {"lookahead_narrow_trips": "sim.lookahead.narrow_trips",
         "lookahead_lockstep_trips": "sim.lookahead.lockstep_trips"}
LAYER_METRICS = os.path.join(harness.BENCH_DIR, "layer_metrics")
BENCH = json.load(open(os.path.join(bench_tiny.REPO, "BENCHMARK.json")))
CTX = {"spans": {"bench": {"epoch": [(0.0, 1.0), (1.0, 2.0)]}}}


def _spec(name):
    return harness.read_json(os.path.join(LAYER_METRICS, name + ".json"))


def test_metric_is_a_ratio_of_two_per_epoch_counters():
    spec = _spec(METRIC)
    assert spec["source"] == {"kind": "metric_ratio",
                              "num": "lookahead_narrow_trips",
                              "den": "lookahead_lockstep_trips"}
    assert (spec["scale"], spec["unit"], spec["layer"], spec["moves"]) \
        == (100, "%", "device collection", "train_env_steps_per_s")
    kinds = {f[:-3] for f in os.listdir(os.path.join(harness.BENCH_DIR,
                                                     "sources"))}
    assert spec["source"]["kind"] in kinds
    for part, counter in PARTS.items():
        source = _spec(part)["source"]
        assert source == {"kind": "telemetry_counter", "counter": counter,
                          "per_epoch": True}
        assert source["kind"] in kinds
    # the new numerator is listed for no cell on its own
    assert "lookahead_narrow_trips" not in {
        m["name"] for m in BENCH["per_layer"]}


def test_metric_is_listed_for_every_cell_behind_what_was_there():
    """Found by name: a later PR's entries may follow."""
    entry, = [m for m in BENCH["per_layer"] if m["name"] == METRIC]
    spec = _spec(METRIC)
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert (entry["unit"], entry["layer"], entry["moves"]) \
        == (spec["unit"], spec["layer"], spec["moves"])
    assert (entry["better"], entry["source"]) == ("higher",
                                                  "program_counter")
    cells = [w["name"] for w in BENCH["workloads"]]
    assert entry["workloads"] == cells[:len(entry["workloads"])]
    assert len(entry["workloads"]) >= 7
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(METRIC) > names.index(
        "decision_ragged_offered_share")
    efficiency, = [m for m in BENCH["per_layer"]
                   if m["name"] == "lookahead_lockstep_efficiency"]
    assert entry["layer"] == efficiency["layer"]


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_reports_the_metric_last_of_what_it_reported(cell):
    names = [m["name"] for m in harness.load_cell(cell).per_layer]
    assert METRIC in names and len(set(names)) == len(names)
    listed = [m["name"] for m in BENCH["per_layer"]
              if cell in m.get("workloads", [cell])]
    assert names == listed


def test_metric_reads_the_two_counters_and_nothing_from_an_older_program():
    from ddls_tpu import telemetry

    telemetry.disable()
    telemetry.reset()
    try:
        telemetry.enable()
        # a program older than the counter (the parent): nothing to
        # read, nothing raised, the metric left out of the line
        telemetry.inc("sim.lookahead.lockstep_trips", 200)
        assert harness.read_layer_metric("lookahead_narrow_trips",
                                         CTX) is None
        assert harness.read_layer_metric(METRIC, CTX) is None
        telemetry.inc("sim.lookahead.narrow_trips", 150)
        assert harness.read_layer_metric("lookahead_narrow_trips",
                                         CTX) == 75     # per epoch
        assert harness.read_layer_metric(METRIC, CTX) == 75.0
        # no trips at all (every lane a memo hit): no share to give
        telemetry.reset()
        telemetry.inc("sim.lookahead.narrow_trips", 0)
        telemetry.inc("sim.lookahead.lockstep_trips", 0)
        assert harness.read_layer_metric(METRIC, CTX) is None
    finally:
        telemetry.disable()
        telemetry.reset()


def test_tiny_fused_run_traced_reports_the_share(tiny_tree, capsys):
    """8 servers under a block side of 8: the channel table has one
    width at the tiny size, so every trip of the (lane-packed) lockstep
    counts as narrow and the share reads 100."""
    from ddls_tpu import telemetry
    from ddls_tpu.telemetry import startup

    startup.registry().reset()
    result, lines = test_bench_run._result(
        capsys, test_bench_run._argv("tiny.fused", 1))
    test_bench_run._check_line(result, traced=True)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics[METRIC] == 100
    counters = telemetry.snapshot()["counters"]
    assert counters["sim.lookahead.lockstep_trips"] > 0
    assert metrics[METRIC] == pytest.approx(
        100 * counters["sim.lookahead.narrow_trips"]
        / counters["sim.lookahead.lockstep_trips"])
    assert sum(v for k, v in counters.items()
               if k.startswith("sim.lookahead.rode.")) > 0
    started, = [json.loads(line[len("[startup] "):]) for line in lines
                if line.startswith("[startup] ")]
    assert started["sim.lookahead.channel_widths"] == [8]
