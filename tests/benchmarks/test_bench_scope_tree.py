"""The device program's scopes as a TREE with self times (PR 50):
``benchmarks/reduce/scope_tree.py`` and the one source kind that reads
it, on small xplanes written through the real file format (the stat the
v5e trace carries: ``tf_op`` on the event's metadata; a ``while`` and a
compiler-inserted copy carry none), and the nine metric files against
the tree the program states (``ddls_tpu/telemetry/scopes.py:TREE``)."""
import json
import os

import pytest

import bench_tiny
import test_bench_scopes
from benchmarks import harness
from benchmarks.reduce import op_scopes, scope_tree, xplane as X
from benchmarks.sources import trace_scope_self
from ddls_tpu.telemetry import scopes

LAYER_METRICS = test_bench_scopes.LAYER_METRICS
write_scoped_xplane = test_bench_scopes.write_scoped_xplane

#: metric -> the scope whose self time it reads (None: the root)
TREE_METRICS = {
    "lookahead_stage_device_s": scopes.SIM_LOOKAHEAD_CALL,
    "lookahead_loop_unnamed_device_s": scopes.SIM_LOOKAHEAD,
    "decision_glue_device_s": scopes.SIM_DECIDE,
    "segment_glue_device_s": scopes.SIM_SEGMENT,
    "epoch_glue_device_s": scopes.ROOT,
    "update_shuffle_device_s": scopes.PPO_SHUFFLE,
    "update_grad_device_s": scopes.PPO_GRAD,
    "update_apply_device_s": scopes.PPO_APPLY,
    "fused_unnamed_device_share": scopes.ROOT,
}

ROUND = "jit(epoch)/while/body/closed_call"
SEGMENT = ROUND + "/vmap(jit(segment))/sim_segment/while/body/closed_call"
DECIDE = SEGMENT + "/sim_decide"
CALL = DECIDE + "/sim_lookahead_call"
LOOP = CALL + "/sim_lookahead/while/body"
UPDATE = ROUND + "/ppo_update/while/body/closed_call"

#: one execution [1000, 3000) ns, as (instruction, op_name, start, end):
#: a round ``while`` [1100, 2900) holding a segment ``while`` [1100,
#: 2000) — sampling, the decision's select, the call's staging, a tick
#: loop with a named gather, an UNNAMED copy and 30 ns of its own, a
#: scatter back — then an unnamed copy of the round's, the update's
#: three parts, the bootstrap's observation and forward, and a
#: top-level unnamed copy behind the loop
OPS = [
    ("%while.1 = () while(r)", None, 1100, 2900),
    ("%while.2 = () while(s)", None, 1100, 2000),
    ("%fusion.10 = f32[] fusion(a)", SEGMENT + "/jit(_gumbel)/add:",
     1100, 1200),
    ("%fusion.11 = f32[] fusion(b)", DECIDE + "/select_n:", 1200, 1300),
    ("%fusion.12 = f32[] fusion(c)", DECIDE + "/sim_price/mul:",
     1300, 1350),
    ("%fusion.13 = f32[] fusion(d)", CALL + "/gather:", 1350, 1450),
    ("%while.3 = () while(t)", None, 1450, 1800),
    ("%fusion.14 = f32[] fusion(e)", LOOP + "/gather:", 1460, 1600),
    ("%copy.15 = f32[] copy(f)", None, 1600, 1700),
    ("%fusion.14 = f32[] fusion(e)", LOOP + "/gather:", 1700, 1780),
    ("%fusion.16 = f32[] fusion(g)", CALL + "/scatter:", 1800, 1900),
    ("%fusion.17 = f32[] fusion(h)",
     SEGMENT + "/sim_memo_probe/scatter:", 1900, 1950),
    ("%copy.18 = f32[] copy(i)", None, 1950, 2000),
    ("%copy.19 = f32[] copy(j)", None, 2000, 2100),
    ("%fusion.20 = f32[] fusion(k)", ROUND + "/ppo_update/ppo_shuffle"
     "/gather:", 2100, 2200),
    ("%fusion.21 = f32[] fusion(l)",
     UPDATE + "/transpose(jvp(ppo_grad))/dot_general:", 2200, 2600),
    ("%fusion.22 = f32[] fusion(m)", UPDATE + "/ppo_apply/add:",
     2600, 2700),
    ("%fusion.23 = f32[] fusion(n)", ROUND + "/ppo_update/reduce_sum:",
     2700, 2750),
    ("%fusion.25 = f32[] fusion(u)", ROUND + "/vmap(env_obs)/mul:",
     2750, 2800),
    ("%fusion.26 = f32[] fusion(v)",
     ROUND + "/policy_forward/GNNPolicy/dot_general:", 2800, 2850),
    ("%copy.24 = f32[] copy(o)", None, 2900, 2950),
]
MODULES = [("jit_epoch(77)", 1000, 3000), ("jit_other(5)", 5000, 5100)]


def _executions(tmp_path, ops=OPS, modules=MODULES):
    path = str(tmp_path / "host.xplane.pb")
    write_scoped_xplane(path, ops, modules)
    device, = op_scopes.load_device_ops(path)
    return scope_tree.executions(device, r"^jit_epoch\(")


def _self(run, scope, **kw):
    return round(scope_tree.self_seconds(
        run, scope, scopes.TREE.get(scope, ()), **kw) * 1e9)


def test_self_time_is_the_interval_less_the_children(tmp_path):
    run, = _executions(tmp_path)
    assert run.events == len(OPS) and run.overlaps == 0
    # the call: gather 100 + scatter 100 — its tick loop is a child
    assert _self(run, scopes.SIM_LOOKAHEAD_CALL) == 200
    # the loop's three named ops are a leaf scope's: 140 + 80, with the
    # copy's 100 and the while's own 350 - 320
    assert _self(run, scopes.SIM_LOOKAHEAD) == 220 + 100 + 30
    # the decision: the select alone (pricing and the call are children)
    assert _self(run, scopes.SIM_DECIDE) == 100
    # the segment: sampling 100, its last copy 50, and the segment
    # while's own time 900 - 900 = 0
    assert _self(run, scopes.SIM_SEGMENT) == 150
    # the update's own: the metric mean; its parts are children
    assert _self(run, scopes.PPO_UPDATE) == 50
    assert [_self(run, s) for s in (scopes.PPO_SHUFFLE, scopes.PPO_GRAD,
                                    scopes.PPO_APPLY)] == [100, 400, 100]
    # the root: the round while's own 1800 - 900 - 100 - 750 = 50, its
    # copy 100, the top-level copy 50, the gaps 100 + 50
    assert _self(run, scopes.ROOT) == 350


def test_a_pathless_node_inherits_its_containers_common_prefix(tmp_path):
    run, = _executions(tmp_path)
    by_name = {(X.short_op_name(name), path): (inherited, ps)
               for (path, inherited, name), ps in run.self_ps.items()}
    # inside the tick loop: the common prefix of its two named gathers
    assert by_name[("copy.15", LOOP + "/gather:")] == (True, 100_000)
    # a container's path is the common prefix of what IT holds
    assert by_name[("while.3", LOOP + "/gather:")] == (True, 30_000)
    # the segment scan's last copy: what the scan's body holds
    assert by_name[("copy.18", SEGMENT)] == (True, 50_000)
    # the round's copy inherits the round's prefix and stays the root's
    assert by_name[("copy.19", ROUND)] == (True, 100_000)
    assert by_name[("while.1", ROUND)] == (True, 50_000)
    # a top-level one has no container: no path at all
    assert by_name[("copy.24", "")] == (False, 50_000)
    assert _self(run, scopes.SIM_LOOKAHEAD, inherited="only") == 130
    assert _self(run, scopes.SIM_SEGMENT, inherited="only") == 50
    assert _self(run, scopes.ROOT, inherited="only") == 150
    assert _self(run, scopes.PPO_GRAD, inherited="only") == 0
    # under no path: the top-level copy and the gaps
    assert _self(run, scopes.ROOT, pathless=True) == 50 + 150
    with pytest.raises(ValueError, match="inherited"):
        scope_tree.self_seconds(run, None, (), inherited="without")


def test_a_fragment_path_stands_behind_its_container_and_has_no_vote(
        tmp_path):
    """The compiler keeps only the tail of some paths
    (``sim_decide/sim_lookahead_call/…`` with no ``jit(epoch)/…``
    before it): such an operation counts for the scopes it names, under
    the scopes its container names, and does not shorten what the
    container's unnamed operations inherit."""
    ops = [op for op in OPS if op[0] != "%copy.15 = f32[] copy(f)"]
    ops.insert(8, ("%fusion.30 = f32[] fusion(p)",
                   "sim_decide/sim_lookahead_call/sim_lookahead/while/"
                   "body/reduce_max:", 1600, 1700))
    run, = _executions(tmp_path, ops)
    assert _self(run, scopes.SIM_LOOKAHEAD) == 320 + 30
    assert _self(run, scopes.SIM_LOOKAHEAD, inherited="only") == 30
    assert _self(run, scopes.ROOT) == 350
    assert _self(run, scopes.SIM_SEGMENT) == 150


def test_every_picosecond_is_counted_once(tmp_path):
    """Σ self times (the execution's own — the gaps — among them) = the
    module's duration, to the picosecond, on the nested plane and on
    one whose events overlap without nesting."""
    run, = _executions(tmp_path)
    assert run.total_ps() == run.duration_ps == 2_000_000
    named = sum(_self(run, s) for s in (
        scopes.ROOT, scopes.SIM_SEGMENT, scopes.SIM_DECIDE,
        scopes.SIM_LOOKAHEAD_CALL, scopes.SIM_LOOKAHEAD, scopes.SIM_PRICE,
        scopes.SIM_MEMO_PROBE, scopes.PPO_UPDATE, scopes.PPO_SHUFFLE,
        scopes.PPO_GRAD, scopes.PPO_APPLY, scopes.ENV_OBS,
        scopes.POLICY_FORWARD))
    assert named == 2000
    # two executions: each its own tree; an event of another program's
    # interval is in neither
    later = [(n, o, s + 4000, e + 4000) for n, o, s, e in OPS]
    runs = _executions(tmp_path, OPS + later + [
        ("%fusion.40 = f32[] fusion(q)", ROUND + "/add:", 3500, 3600)],
        MODULES[:1] + [("jit_epoch(77)", 5000, 7000)])
    assert [r.total_ps() for r in runs] == [2_000_000, 2_000_000]
    assert runs[0].self_ps == runs[1].self_ps


def test_an_overlapping_event_is_counted_and_reported(tmp_path):
    """An event that starts inside its predecessor and ends after it is
    cut to what lies behind the predecessor's end — counted, reported,
    and the sum still closes; one that runs past the execution's end is
    cut there."""
    ops = [
        ("%fusion.1 = f32[] fusion(a)", ROUND + "/add:", 1000, 1400),
        ("%fusion.2 = f32[] fusion(b)", ROUND + "/ppo_update/mul:",
         1300, 1700),
        ("%fusion.3 = f32[] fusion(c)", ROUND + "/sub:", 2900, 3100),
    ]
    run, = _executions(tmp_path, ops)
    assert (run.events, run.overlaps, run.overlap_ps) == (3, 1, 100_000)
    assert _self(run, scopes.PPO_UPDATE) == 300
    assert _self(run, scopes.ROOT) == 2000 - 300
    assert run.total_ps() == run.duration_ps


# ------------------------------------------------------------- source
@pytest.fixture()
def tree_ctx(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    cell = bench_tiny.unlisted_cell("pacml_ramp32_dev",
                                    "train_fused_8x32")
    trace_dir = tmp_path / "trace" / cell.name / "plugins"
    trace_dir.mkdir(parents=True)
    path = str(trace_dir / "host.xplane.pb")
    # the second execution ends 40 ns later: a longer last gap
    later = [(n, o, s + 4000, e + 4000) for n, o, s, e in OPS]
    write_scoped_xplane(path, OPS + later,
                        MODULES[:1] + [("jit_epoch(77)", 5000, 7040)])
    return {"cell": cell, "trace": X.Trace.from_file(path)}


def test_the_nine_metrics_read_the_tree(tree_ctx):
    got = {name: harness.read_layer_metric(name, tree_ctx)
           for name in TREE_METRICS}
    assert got == {
        "lookahead_stage_device_s": pytest.approx(200e-9),
        "lookahead_loop_unnamed_device_s": pytest.approx(130e-9),
        "decision_glue_device_s": pytest.approx(100e-9),
        "segment_glue_device_s": pytest.approx(150e-9),
        "epoch_glue_device_s": pytest.approx((350e-9 + 390e-9) / 2),
        "update_shuffle_device_s": pytest.approx(100e-9),
        "update_grad_device_s": pytest.approx(400e-9),
        "update_apply_device_s": pytest.approx(100e-9),
        # 200 of 2,000 ns and 240 of 2,040: the median of two
        "fused_unnamed_device_share": pytest.approx(
            100 * (0.1 + 240 / 2040) / 2),
    }
    # the xplane was parsed once and the tree built once a program
    assert list(tree_ctx["scope_trees"]) == ["holds", r"^jit_epoch\("]
    assert len(tree_ctx["scope_trees"][r"^jit_epoch\("]) == 2


def test_a_program_without_the_tree_reads_nothing(tmp_path, monkeypatch):
    """The parent's program — or a stale executable out of a compile
    cache keyed without the names — carries the eight leaf scopes and
    none of the tree's: a metric of a new scope is None, not a number
    of something else, and nothing raises. The one metric that reads an
    OLD scope (what the tick loops' unnamed operations inherit) reads
    the parent's loops as it reads the change's."""
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    cell = bench_tiny.unlisted_cell("pacml_ramp32_dev",
                                    "train_fused_8x32")
    trace_dir = tmp_path / "trace" / cell.name / "plugins"
    trace_dir.mkdir(parents=True)
    old = [(n, o and o.replace("/sim_segment", "")
            .replace("/sim_decide", "").replace("/sim_lookahead_call", "")
            .replace("/ppo_shuffle", "").replace("(ppo_grad)", "()")
            .replace("/ppo_apply", ""), s, e) for n, o, s, e in OPS]
    path = str(trace_dir / "host.xplane.pb")
    write_scoped_xplane(path, old, MODULES)
    ctx = {"cell": cell, "trace": X.Trace.from_file(path)}
    got = {name: harness.read_layer_metric(name, ctx)
           for name in TREE_METRICS}
    assert got.pop("lookahead_loop_unnamed_device_s") == \
        pytest.approx(130e-9)
    assert set(got.values()) == {None}
    # the old reader still reads the old scope there
    assert harness.read_layer_metric("lookahead_device_s", ctx) == \
        pytest.approx(220e-9)
    assert trace_scope_self.read(
        {"program": r"^jit_epoch\(", "scope": "sim_decide",
         "children": []}, {"trace": None}) is None
    with pytest.raises(ValueError, match="share"):
        trace_scope_self.read(
            {"program": r"^jit_epoch\(", "scope": "ppo_update",
             "children": [], "share": "complement"}, ctx)


# ------------------------------------------------- files and entries
@pytest.mark.parametrize("name", sorted(TREE_METRICS))
def test_metric_files_state_the_programs_tree(name):
    """Each file's ``scope`` / ``children`` are the program's own tree:
    a scope renamed or moved in ``scopes.TREE`` fails here, not as a
    metric that silently reads nothing."""
    spec = harness.read_json(os.path.join(LAYER_METRICS, name + ".json"))
    source = spec["source"]
    assert source["kind"] == "trace_scope_self"
    assert source["program"] == harness.read_json(os.path.join(
        LAYER_METRICS, "fused_epoch_device_s.json"))["source"]["match"]
    scope = TREE_METRICS[name]
    assert source.get("scope", scopes.ROOT) == scope
    assert tuple(source["children"]) == scopes.TREE.get(scope, ())
    assert set(source) <= {"kind", "program", "scope", "children",
                           "inherited", "pathless", "stat", "share"}


def test_every_scope_of_the_tree_has_a_metric_that_reads_it():
    """A scope goes into the tree WITH a committed metric that reads it,
    or not in: the enclosing scopes by their self time (this PR's
    files), the eight leaves by the flat reader's (PR 23)."""
    read = set(TREE_METRICS.values())
    for name in os.listdir(LAYER_METRICS):
        source = harness.read_json(
            os.path.join(LAYER_METRICS, name))["source"]
        if source["kind"] == "trace_scope_time":
            read |= set(source["scopes"])
    named = set(scopes.TREE) | {c for cs in scopes.TREE.values()
                                for c in cs}
    assert named <= read, named - read
    assert named - {scopes.ROOT} - set(scopes.ALL) == {
        scopes.SIM_SEGMENT, scopes.SIM_DECIDE, scopes.SIM_LOOKAHEAD_CALL,
        scopes.PPO_SHUFFLE, scopes.PPO_GRAD, scopes.PPO_APPLY}


def test_entries_are_appended_for_all_cells_and_the_older_untouched():
    bench = json.load(open(os.path.join(bench_tiny.REPO,
                                        "BENCHMARK.json")))
    cells = [w["name"] for w in bench["workloads"]]
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index("lookahead_stage_device_s")
    assert names[first - 1] == "lookahead_trips_per_op"   # PR 48's last
    assert names[first:first + len(TREE_METRICS)] == list(TREE_METRICS)
    for metric in bench["per_layer"][first:first + len(TREE_METRICS)]:
        spec = harness.read_json(os.path.join(
            LAYER_METRICS, metric["name"] + ".json"))
        assert metric == {
            "name": metric["name"], "unit": spec["unit"],
            "better": "lower", "source": "device_trace",
            "layer": spec["layer"], "moves": "train_env_steps_per_s",
            "workloads": cells}
    # what stood before this PR's entries is what the parent had, to
    # the entry (PR 49's list: 51 metrics, the last one longcat's)
    assert first == 51
    assert bench["per_layer"][first - 1]["workloads"] == [cells[-1]]
