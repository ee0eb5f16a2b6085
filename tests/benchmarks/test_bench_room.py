"""Room for the next cell (ROADMAP Y10): with a cell, its configuration
and a metric of its own APPENDED behind ``sala`` — what a `model_config`
PR does, which may edit no file the benchmark has — every test that
pins what an older PR left still holds. Each cell's tests find their
entries by name and pin what their PR appended as a prefix, so no shim
hands them an older benchmark (``tests/benchmarks/conftest.py`` did
until PR 47, for ``test_bench_trinity.py``'s `workloads[-1]`,
`configs[-1]`, `per_layer[-1]`)."""
import copy
import json
import os

import pytest

import bench_tiny
import test_bench_epoch_anatomy
import test_bench_glm5
import test_bench_mimo
import test_bench_narrow
import test_bench_narrowest
import test_bench_sala
import test_bench_trinity
from benchmarks import harness

REPO = bench_tiny.REPO
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
LAST = "sala_ramp32.train_fused"
NEXT = "next_ramp32.train_fused"


def appended(bench: dict) -> dict:
    """``bench`` as the next `model_config` PR would leave it: one more
    configuration and cell (standing on ``sala``'s files: only the
    lists matter here), the cell's name behind ``sala``'s on every list
    that has it, and one per-layer metric for the new cell alone."""
    out = copy.deepcopy(bench)
    config = next(c for c in out["configs"]
                  if c["name"] == "minicpm_sala_whole_ramp32")
    out["configs"].append(dict(config, name="next_whole_ramp32"))
    cell = next(w for w in out["workloads"] if w["name"] == LAST)
    out["workloads"].append(dict(cell, name=NEXT,
                                 config="next_whole_ramp32"))
    for metric in out["end_to_end"] + out["per_layer"]:
        if LAST in metric.get("workloads", ()):
            metric["workloads"].append(NEXT)
    out["per_layer"].append({
        "name": "next_cells_own_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "device collection",
        "moves": "train_env_steps_per_s", "workloads": [NEXT]})
    return out


@pytest.fixture()
def benchmark_with_a_cell_appended(monkeypatch):
    """Every module's copy of the benchmark, and the one `load_cell`
    reads from the repo, with the next cell appended."""
    listed = os.path.join(harness.REPO, "BENCHMARK.json")
    read_json = harness.read_json
    monkeypatch.setattr(
        harness, "read_json",
        lambda path: appended(read_json(path))
        if os.path.abspath(path) == listed else read_json(path))
    later = appended(BENCH)
    for module in (test_bench_glm5, test_bench_mimo, test_bench_trinity,
                   test_bench_sala, test_bench_narrow,
                   test_bench_narrowest, test_bench_epoch_anatomy):
        if hasattr(module, "BENCH"):
            monkeypatch.setattr(module, "BENCH", later)
    monkeypatch.setattr(test_bench_sala, "PARENT",
                        test_bench_sala.parent_of(later))
    return later


def test_the_appended_cell_resolves_and_reports_what_salas_does(
        benchmark_with_a_cell_appended):
    cell = harness.load_cell(NEXT)
    assert cell.config_name == "next_whole_ramp32"
    names = [m["name"] for m in cell.per_layer]
    assert names[:-1] == [m["name"]
                          for m in harness.load_cell(LAST).per_layer]
    assert names[-1] == "next_cells_own_share"
    assert [w["name"] for w in benchmark_with_a_cell_appended[
        "workloads"]][-2:] == [LAST, NEXT]


PINS = [
    (test_bench_glm5, "test_old_cells_report_what_they_reported", ()),
    (test_bench_mimo, "test_old_cells_report_what_they_reported", ()),
    (test_bench_trinity, "test_old_cells_report_what_they_reported", ()),
    (test_bench_trinity,
     "test_new_metric_is_data_of_reader_kinds_that_exist", ()),
    (test_bench_sala,
     "test_cell_reports_every_metric_trinitys_does_and_the_two_new", ()),
    (test_bench_sala,
     "test_this_pr_appended_and_old_cells_report_what_they_reported", ()),
    (test_bench_narrow,
     "test_metric_is_listed_for_every_cell_behind_what_was_there", ()),
    (test_bench_narrow,
     "test_cell_reports_the_metric_last_of_what_it_reported", (LAST,)),
    (test_bench_narrow,
     "test_cell_reports_the_metric_last_of_what_it_reported", (NEXT,)),
    (test_bench_narrowest,
     "test_the_seven_cells_list_it_behind_the_narrow_share", ()),
    (test_bench_narrowest,
     "test_cell_reports_the_share_once_and_in_the_listed_order",
     ("sala_ramp32",)),
    (test_bench_epoch_anatomy,
     "test_the_four_displaced_nothing_the_old_cells_reported", ()),
]


@pytest.mark.parametrize(
    "module, name, args", PINS,
    ids=[f"{m.__name__[len('test_bench_'):]}.{n[len('test_'):][:48]}"
         + ("." + a[0].split(".")[0] if a else "") for m, n, a in PINS])
def test_an_older_prs_pins_hold_with_a_cell_appended(
        benchmark_with_a_cell_appended, module, name, args):
    getattr(module, name)(*args)


@pytest.mark.parametrize("module, name, args", PINS[:7],
                         ids=[n[len("test_"):][:56] + "." + m.__name__[
                             len("test_bench_"):] for m, n, a in PINS[:7]])
def test_the_same_pins_hold_on_the_benchmark_as_it_is(module, name, args):
    """Driven from here no shim of ``tests/conftest.py`` applies (it
    hides the two trip shares from three modules by their names): the
    pins hold on the whole benchmark, so that shim can go too."""
    getattr(module, name)(*args)
