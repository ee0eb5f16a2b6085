"""``lookahead_narrowest_trip_share`` (PR 45): the share of the
lockstep's trips that ran over the FIRST rung of the channel table, in
``lookahead_narrow_trip_share``'s form (``test_bench_narrow.py``) — DATA
of reader kinds the benchmark already has (a ratio of two of the
program's telemetry counters, both per epoch, so the window's epochs
cancel), listed for the seven cells behind what was there; a program
without the counter (the parent) reads nothing and does not raise, and a
tiny fused run traced on the CPU reports it beside the counters it is
made of."""
import json
import os

import pytest

import bench_tiny
import test_bench_run
from benchmarks import harness
from test_bench_run import restore_process_state, tiny_tree  # noqa: F401

METRIC = "lookahead_narrowest_trip_share"
PARTS = {"lookahead_narrowest_trips": "sim.lookahead.narrowest_trips",
         "lookahead_lockstep_trips": "sim.lookahead.lockstep_trips"}
SEVEN = ["ramp32_dev", "ramp32_load32", "olmoe_ramp32", "glm5_ramp32",
         "mimo_ramp32", "trinity_ramp32", "sala_ramp32"]
LAYER_METRICS = os.path.join(harness.BENCH_DIR, "layer_metrics")
BENCH = json.load(open(os.path.join(bench_tiny.REPO, "BENCHMARK.json")))
CTX = {"spans": {"bench": {"epoch": [(0.0, 1.0), (1.0, 2.0)]}}}


def _spec(name):
    return harness.read_json(os.path.join(LAYER_METRICS, name + ".json"))


def test_the_two_data_files_load_as_a_ratio_of_per_epoch_counters():
    spec = _spec(METRIC)
    assert spec["source"] == {"kind": "metric_ratio",
                              "num": "lookahead_narrowest_trips",
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
    assert "lookahead_narrowest_trips" not in {
        m["name"] for m in BENCH["per_layer"]}


def test_the_seven_cells_list_it_behind_the_narrow_share():
    """Found by name: a later PR's entries may follow."""
    entry, = [m for m in BENCH["per_layer"] if m["name"] == METRIC]
    spec = _spec(METRIC)
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert (entry["unit"], entry["layer"], entry["moves"]) \
        == (spec["unit"], spec["layer"], spec["moves"])
    assert (entry["better"], entry["source"]) == ("higher",
                                                  "program_counter")
    assert entry["workloads"][:7] == [c + ".train_fused" for c in SEVEN]
    cells = [w["name"] for w in BENCH["workloads"]]
    assert entry["workloads"] == cells[:len(entry["workloads"])]
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(METRIC) > names.index("lookahead_narrow_trip_share")
    narrow, = [m for m in BENCH["per_layer"]
               if m["name"] == "lookahead_narrow_trip_share"]
    assert entry["layer"] == narrow["layer"]


@pytest.mark.parametrize("cell", SEVEN)
def test_cell_reports_the_share_once_and_in_the_listed_order(cell):
    cell += ".train_fused"
    names = [m["name"] for m in harness.load_cell(cell).per_layer]
    assert names.count(METRIC) == 1
    assert names == [m["name"] for m in BENCH["per_layer"]
                     if cell in m.get("workloads", [cell])]
    assert names.index(METRIC) > names.index("lookahead_narrow_trip_share")


def test_ratio_reads_100_x_num_over_den_and_nothing_from_the_parent():
    from ddls_tpu import telemetry

    telemetry.disable()
    telemetry.reset()
    try:
        telemetry.enable()
        # a program older than the counter (the parent, which counts
        # `narrow_trips` alone): nothing to read, nothing raised, the
        # metric left out of the line
        telemetry.inc("sim.lookahead.lockstep_trips", 200)
        telemetry.inc("sim.lookahead.narrow_trips", 150)
        assert harness.read_layer_metric("lookahead_narrowest_trips",
                                         CTX) is None
        assert harness.read_layer_metric(METRIC, CTX) is None
        telemetry.inc("sim.lookahead.narrowest_trips", 90)
        assert harness.read_layer_metric("lookahead_narrowest_trips",
                                         CTX) == 45     # per epoch
        assert harness.read_layer_metric(METRIC, CTX) == 45.0
        assert harness.read_layer_metric("lookahead_narrow_trip_share",
                                         CTX) == 75.0
        # no trips at all (every lane a memo hit): no share to give
        telemetry.reset()
        telemetry.inc("sim.lookahead.narrowest_trips", 0)
        telemetry.inc("sim.lookahead.lockstep_trips", 0)
        assert harness.read_layer_metric(METRIC, CTX) is None
    finally:
        telemetry.disable()
        telemetry.reset()


def test_tiny_fused_run_traced_reports_the_share(tiny_tree, capsys):
    """`bench_tiny`'s counters: 8 servers, which no rung of the channel
    table is under (a rung under a register's 8 sublanes is none), so
    the one table there is the first rung and the share reads 100 x
    narrowest_trips / lockstep_trips = 100."""
    from ddls_tpu import telemetry
    from ddls_tpu.telemetry import startup

    startup.registry().reset()
    result, lines = test_bench_run._result(
        capsys, test_bench_run._argv("tiny.fused", 1))
    test_bench_run._check_line(result, traced=True)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    counters = telemetry.snapshot()["counters"]
    assert counters["sim.lookahead.lockstep_trips"] > 0
    assert metrics[METRIC] == pytest.approx(
        100 * counters["sim.lookahead.narrowest_trips"]
        / counters["sim.lookahead.lockstep_trips"]) == 100
    assert counters["sim.lookahead.narrowest_trips"] \
        == counters["sim.lookahead.narrow_trips"]
    started, = [json.loads(line[len("[startup] "):]) for line in lines
                if line.startswith("[startup] ")]
    assert started["sim.lookahead.channel_widths"] == [8]
