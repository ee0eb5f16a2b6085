"""The per-layer metrics that read what the program names itself (PR
23): each new source kind on a small xplane written through the real
file format WITH the stat the v5e trace carried (``tf_op`` on the
event's metadata, never on the event), the scope list against the
program's, and the fused path traced end to end at a tiny size."""
import json
import os

import pytest

import bench_tiny
import test_bench_run
from benchmarks import harness
from benchmarks.reduce import op_scopes, xplane as X
from benchmarks.sources import (metric_ratio, startup_span,
                                telemetry_counter,
                                trace_idle_outside_spans,
                                trace_scope_time)

tiny_tree = test_bench_run.tiny_tree
restore_process_state = test_bench_run.restore_process_state

LAYER_METRICS = os.path.join(harness.BENCH_DIR, "layer_metrics")
NEW_METRICS = (
    "lookahead_device_s", "placement_device_s", "pricing_device_s",
    "memo_probe_device_s", "advance_device_s", "fused_forward_device_s",
    "fused_update_device_s", "fused_unscoped_device_share",
    "lookahead_lockstep_trips", "lookahead_lockstep_efficiency",
    "lookahead_trip_device_ms", "setup_job_banks_s",
    "setup_trace_lower_s", "setup_first_epoch_s", "setup_build_run_s",
    "setup_before_build_s",
    "device_idle_unattributed_share")

#: as the chip wrote them (my chip run, PR 23): the scope path ends in
#: the primitive and a colon; a ``while`` and a data-formatting copy
#: carry none
LOOKAHEAD = ("jit(epoch)/while/body/closed_call/vmap(sim_lookahead)"
             "/while/body/gather:")
UPDATE = "jit(epoch)/transpose(jvp(ppo_update))/while/body/dot_general:"
FORWARD = "jit(epoch)/while/body/closed_call/policy_forward/dot_general:"


def _quote(text):
    return '"' + text.replace("\\", "\\\\").replace('"', '\\"') + '"'


def write_scoped_xplane(path, ops, modules, host_spans=()):
    """An xplane with one device plane whose instructions carry
    ``tf_op`` on their METADATA: ``ops`` = (instruction, op_name or
    None, start_ns, end_ns)."""
    from jax.profiler import ProfileData

    ids, body = {}, ['  name: "/device:TPU:0"']
    for line_id, (line, events) in enumerate(
            (("XLA Ops", [(n, s, e) for n, _, s, e in ops]),
             ("XLA Modules", modules)), start=1):
        body.append(f"  lines {{ id: {line_id} name: {_quote(line)} "
                    "timestamp_ns: 0")
        for name, start, end in events:
            mid = ids.setdefault(name, len(ids) + 1)
            body.append(f"    events {{ metadata_id: {mid} offset_ps: "
                        f"{start * 1000} duration_ps: "
                        f"{(end - start) * 1000} }}")
        body.append("  }")
    op_name_of = {n: o for n, o, _, _ in ops}
    for name, mid in ids.items():
        stat = ""
        if op_name_of.get(name):
            stat = (" stats { metadata_id: 7 str_value: "
                    f"{_quote(op_name_of[name])} }}")
        body.append(f"  event_metadata {{ key: {mid} value {{ id: {mid} "
                    f"name: {_quote(name)}{stat} }} }}")
    body.append('  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }')
    text = "planes {\n" + "\n".join(body) + "\n}\n"
    if host_spans:
        text += X.to_text_proto([X.Plane("/host:CPU", [X.Line(
            "main/1", [X.Event(*s) for s in host_spans])])])
    with open(path, "wb") as fh:
        fh.write(ProfileData.text_proto_to_serialized_xspace(text))


@pytest.fixture()
def scoped_ctx(tmp_path, monkeypatch):
    """Two executions of jit_epoch: a lookahead ``while`` (no scope of
    its own) holding two scoped gathers with a gap between them, a
    forward, an update, an unscoped copy."""
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    cell = bench_tiny.unlisted_cell("pacml_ramp32_dev",
                                    "train_fused_8x32")
    trace_dir = tmp_path / "trace" / cell.name / "plugins"
    trace_dir.mkdir(parents=True)
    path = str(trace_dir / "host.xplane.pb")
    ops = []
    for t0 in (1000, 3000):
        ops += [("%while.9 = () while(x)", None, t0 + 100, t0 + 700),
                ("%fusion.1 = f32[] fusion(a)", LOOKAHEAD, t0 + 100,
                 t0 + 300),
                ("%fusion.1 = f32[] fusion(a)", LOOKAHEAD, t0 + 400,
                 t0 + 700),
                ("%fusion.2 = f32[] fusion(b)", FORWARD, t0 + 700,
                 t0 + 750),
                ("%fusion.3 = f32[] fusion(c)", UPDATE, t0 + 750,
                 t0 + 900),
                ("%copy.4 = f32[] copy(d)", None, t0 + 900, t0 + 1000)]
    write_scoped_xplane(
        path, ops,
        [("jit_epoch(77)", 1000, 2000), ("jit_epoch(77)", 3000, 4000),
         ("jit_other(5)", 5000, 5100)],
        host_spans=[("bench.trace_window", 0, 6000),
                    ("ddls.train.fused_epoch", 900, 2500),
                    ("ddls.train.host_sync", 4000, 4500)])
    return {"cell": cell, "trace": X.Trace.from_file(path),
            "spans": {"bench": {"epoch": [1.0, 1.0]}}}


def _scope_source(scopes, **kw):
    return {"kind": "trace_scope_time", "program": r"^jit_epoch\(",
            "scopes": scopes, "stat": "median", **kw}


def test_scope_time_unions_wrapped_scopes_inside_containers(scoped_ctx):
    # the while holds 600 ns, its scoped body ran 500 ns of them: the
    # container has no scope and adds nothing; vmap(...) is matched
    assert trace_scope_time.read(
        _scope_source(["sim_lookahead"]), scoped_ctx) == \
        pytest.approx(500e-9)
    assert trace_scope_time.read(
        _scope_source(["ppo_update"]), scoped_ctx) == pytest.approx(150e-9)
    assert trace_scope_time.read(
        _scope_source(["policy_forward", "env_obs"]), scoped_ctx) == \
        pytest.approx(50e-9)
    # 700 of an execution's 1,000 ns carry a scope: the copy (100) and
    # the gap inside the while (100), and the first 100, do not
    assert trace_scope_time.read(_scope_source(
        ["sim_lookahead", "policy_forward", "ppo_update"],
        share="complement"), scoped_ctx) == pytest.approx(0.3)
    # a scope no operation carries is nothing to read, not 0 s; a name
    # that is only the PREFIX of a segment does not match
    assert trace_scope_time.read(
        _scope_source(["sim_advance"]), scoped_ctx) is None
    assert trace_scope_time.read(
        _scope_source(["sim_look"]), scoped_ctx) is None
    assert trace_scope_time.read(
        _scope_source(["sim_lookahead"]), {"trace": None}) is None


def test_scope_time_says_so_when_the_harness_moved_the_trace(
        scoped_ctx, tmp_path, monkeypatch):
    """A run with a trace whose file is not where run.py puts it is a
    harness change this reader has to follow: loud, not a metric that
    silently goes missing."""
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path / "moved"))
    with pytest.raises(FileNotFoundError, match="benchmarks/run.py"):
        trace_scope_time.read(_scope_source(["sim_lookahead"]),
                              scoped_ctx)


def test_scope_pattern_matches_segments_wrapped_or_not():
    rx = op_scopes.scope_pattern(["ppo_update", "env_obs"])
    assert rx.search("jit(epoch)/transpose(jvp(ppo_update))/mul:")
    assert rx.search("jit(epoch)/env_obs/gather:")
    assert rx.search("env_obs")
    assert rx.search("jit(epoch)/policy_forward/reshape;vmap(env_obs)/squeeze")
    assert not rx.search("jit(epoch)/my_env_obs/gather:")
    assert not rx.search("jit(epoch)/env_obs_extra/gather:")


def test_wire_reader_agrees_with_profile_data_on_the_recorded_trace():
    """The hand-written reader against jax's own on the recorded v5e
    trace: same events, same times."""
    recorded = os.path.join(harness.BENCH_DIR, "testdata",
                            "train_host_v5e.xplane.pb.gz")
    devices = op_scopes.load_device_ops(recorded)
    trace = X.Trace.from_file(recorded)
    assert [d.plane for d in devices] == [p.name for p in trace.devices]
    want = trace.devices[0].line(X.OPS_LINE).events
    got = devices[0].ops
    assert len(got) == len(want) > 0
    assert [e.name for e in got] == [e.name for e in want]
    for g, w in zip(got[:200], want[:200]):
        assert g.start_ns == pytest.approx(w.start_ns, abs=1.0)
        assert g.end_ns - g.start_ns == pytest.approx(w.duration_ns,
                                                      abs=1.0)


def test_idle_outside_the_programs_spans(scoped_ctx):
    # window 0..6000; busy 1100..2000 and 3100..4000 => idle 4,200 ns,
    # of which ddls.* spans cover 900..1100, 2000..2500, 4000..4500
    value = trace_idle_outside_spans.read({"prefix": "ddls."}, scoped_ctx)
    assert value == pytest.approx(1 - 1200 / 4200)
    assert trace_idle_outside_spans.read({"prefix": "none."},
                                         scoped_ctx) is None


def test_telemetry_counter_reads_the_window(scoped_ctx,
                                            restore_process_state):
    from ddls_tpu import telemetry

    source = {"counter": "sim.lookahead.lockstep_trips"}
    telemetry.reset()
    assert telemetry_counter.read(source, scoped_ctx) is None
    telemetry.enable()
    telemetry.inc("sim.lookahead.lockstep_trips", 200)
    telemetry.disable()          # the registry keeps the window
    assert telemetry_counter.read(source, scoped_ctx) == 200.0
    assert telemetry_counter.read({**source, "per_epoch": True},
                                  scoped_ctx) == 100.0
    assert telemetry_counter.read({**source, "per_epoch": True},
                                  {}) is None


def test_lockstep_efficiency_is_a_ratio_of_two_counters(
        scoped_ctx, restore_process_state):
    from ddls_tpu import telemetry

    telemetry.reset()
    assert harness.read_layer_metric("lookahead_lockstep_efficiency",
                                     scoped_ctx) is None
    telemetry.enable()
    telemetry.inc("sim.lookahead.trips", 900)
    telemetry.inc("sim.lookahead.lockstep_lane_trips", 200 * 8)
    telemetry.disable()
    assert harness.read_layer_metric(
        "lookahead_lockstep_efficiency", scoped_ctx) == \
        pytest.approx(100 * 900 / 1600)


def test_startup_span_unions_nested_intervals():
    from ddls_tpu.telemetry import startup

    reg = startup.registry()
    reg.reset()
    try:
        reg.record_span("startup.jax.trace", 10.0, 14.0)
        reg.record_span("startup.jax.trace", 11.0, 12.0)   # inner jit
        reg.record_span("startup.jax.lower", 14.0, 15.5)
        reg.record_span("startup.job_banks", 2.0, 9.0)
        assert startup_span.read(
            {"names": ["startup.jax.trace", "startup.jax.lower"]},
            {}) == pytest.approx(5.5)
        assert startup_span.read({"names": ["startup.job_banks"]},
                                 {}) == pytest.approx(7.0)
        assert startup_span.read({"names": ["startup.nothing"]},
                                 {}) is None
    finally:
        reg.reset()


def test_metric_ratio_of_two_layer_metrics(monkeypatch):
    values = {"a": 3.0, "b": 1.5, "zero": 0.0, "none": None}
    monkeypatch.setattr(harness, "read_layer_metric",
                        lambda name, ctx: values[name])
    assert metric_ratio.read({"num": "a", "den": "b"}, {}) == 2.0
    assert metric_ratio.read({"num": "a", "den": "zero"}, {}) is None
    assert metric_ratio.read({"num": "none", "den": "b"}, {}) is None


def test_every_matched_scope_is_one_the_program_names():
    from ddls_tpu.telemetry import scopes

    matched = set()
    for name in os.listdir(LAYER_METRICS):
        source = harness.read_json(
            os.path.join(LAYER_METRICS, name))["source"]
        if source["kind"] == "trace_scope_time":
            matched |= set(source["scopes"])
    assert matched and matched <= set(scopes.ALL), matched - set(scopes.ALL)
    unscoped = harness.read_json(os.path.join(
        LAYER_METRICS, "fused_unscoped_device_share.json"))["source"]
    # the remainder is what NO scope names
    assert set(unscoped["scopes"]) == set(scopes.ALL)


def test_new_metrics_are_listed_on_both_cells():
    bench = json.load(open(os.path.join(bench_tiny.REPO,
                                        "BENCHMARK.json")))
    cells = [w["name"] for w in bench["workloads"]]
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert listed[name]["workloads"] == cells, name
        assert os.path.exists(os.path.join(LAYER_METRICS, name + ".json"))


def test_fused_path_traced_reads_the_programs_own_counters(tiny_tree,
                                                           capsys):
    """The traced fused cell at a tiny size: counters and start-up
    spans read on any backend; the CPU has no device plane, so every
    trace metric is left out rather than faked."""
    from ddls_tpu.telemetry import startup

    startup.registry().reset()
    result, _ = test_bench_run._result(
        capsys, test_bench_run._argv("tiny.fused", 1))
    test_bench_run._check_line(result, traced=True)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    metrics_setup_bound = 600.0   # seconds: a span, not a timestamp
    assert {"lookahead_lockstep_trips", "lookahead_lockstep_efficiency",
            "setup_job_banks_s", "setup_trace_lower_s",
            "setup_first_epoch_s", "setup_build_run_s",
            "memo_hit_rate"} <= set(metrics)
    assert metrics["lookahead_lockstep_trips"] > 0
    assert 0 < metrics["lookahead_lockstep_efficiency"] <= 100
    for name in ("setup_job_banks_s", "setup_trace_lower_s",
                 "setup_first_epoch_s", "setup_build_run_s"):
        assert 0 < metrics[name] < metrics_setup_bound, name
    assert metrics["setup_job_banks_s"] < metrics["setup_build_run_s"]
    # the age of THIS process when build_run was entered: the whole
    # pytest session so far, so only its presence is checked
    assert metrics["setup_before_build_s"] > 0
    assert not {"lookahead_device_s", "fused_unscoped_device_share",
                "lookahead_trip_device_ms",
                "device_idle_unattributed_share"} & set(metrics)
