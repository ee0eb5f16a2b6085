"""What every path shares: the cell's files, the recorder of spans and
counters, the compile meter, the device facts, the per-layer metric
readers and the one result line.

Nothing here touches jax at import time (``run.py`` is re-imported by
spawned env workers).
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import os
import time
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)
OUT_DIR = os.path.join(BENCH_DIR, "out")


def read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


# ------------------------------------------------------------- the cell
@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with every file it names, resolved."""
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    run_seconds: int

    @property
    def path(self) -> str:
        return self.traffic["path"]


def _reported_here(metric: dict, cell_name: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or cell_name in cells


def load_cell(workload: str, root: str = REPO) -> Cell:
    """Resolve a cell by the names BENCHMARK.json gives: the
    configuration's file as listed, ``traffic/<mix>.json``, and the
    metrics this cell reports. A per-layer metric is reported only
    where the end-to-end metric it moves is."""
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SystemExit(
            f"unknown workload {workload!r}; BENCHMARK.json has "
            f"{[w['name'] for w in bench['workloads']]}")
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == entry["config"])
    bench_dir = os.path.join(root, bench["paths"][0])
    end_to_end = [m for m in bench["end_to_end"]
                  if _reported_here(m, workload)]
    moved = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if _reported_here(m, workload) and m["moves"] in moved]
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config_name=entry["config"],
        config=read_json(os.path.join(root, config_entry["file"])),
        traffic_name=entry["traffic"],
        traffic=read_json(os.path.join(bench_dir, "traffic",
                                       entry["traffic"] + ".json")),
        end_to_end=end_to_end, per_layer=per_layer,
        run_seconds=int(bench["run_seconds"]))


def lookup(cfg: dict, dotted: str):
    """``a.b.0.c`` into nested dicts and lists."""
    node: Any = cfg
    for key in dotted.split("."):
        node = node[int(key)] if isinstance(node, list) else node[key]
    return node


def check_expectations(cfg: dict, expect: Dict[str, Any]) -> None:
    """The configuration file states the sizes as run; a composed tree
    that differs (a default changed under the benchmark) is an error,
    not a quiet change of the cell."""
    wrong = {k: (lookup(cfg, k), v) for k, v in expect.items()
             if lookup(cfg, k) != v}
    if wrong:
        raise SystemExit(f"composed config differs from the cell's "
                         f"configuration file (got, expected): {wrong}")


def load_path(name: str):
    """``paths/<name>.py``: set-up, warm-up, window and correctness of
    one way of driving the program."""
    return importlib.import_module(f"benchmarks.paths.{name}")


# ------------------------------------------------------- compile meter
class CompileMeter:
    """Compile seconds and persistent-cache hits/misses from jax's own
    monitoring events (copied from ``chip_smoke.py``): how set-up's
    compile part is read and how a compile inside the window shows."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += seconds
            self.compiles += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def totals(self) -> Dict[str, float]:
        """``compiles`` counts every program built, one loaded from the
        persistent cache among them (``compile_s`` is then the load);
        ``cache_misses`` is what really compiled."""
        return {"compile_s": self.compile_s, "compiles": self.compiles,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}

    @staticmethod
    def delta(now: Dict[str, float], then: Dict[str, float]
              ) -> Dict[str, float]:
        return {k: now[k] - then[k] for k in now}


# ------------------------------------------------------------ recorder
class Recorder:
    """The benchmark's own spans. A span is timed on the host clock
    and, while the profiler runs, also written into the trace as
    ``bench.<name>`` so that device gaps can be named by what the host
    was doing."""

    def __init__(self, trace_dir: Optional[str] = None):
        self.trace_dir = trace_dir
        self.spans: Dict[str, List[float]] = {}
        self.tracing = False
        self._window = None

    @contextlib.contextmanager
    def span(self, name: str):
        annotation = contextlib.nullcontext()
        if self.tracing:
            import jax

            annotation = jax.profiler.TraceAnnotation("bench." + name)
        t0 = time.perf_counter()
        try:
            with annotation:
                yield
        finally:
            self.spans.setdefault(name, []).append(
                time.perf_counter() - t0)

    def reset(self) -> None:
        """Start of the window: what set-up recorded is dropped."""
        self.spans.clear()

    def start_trace(self) -> None:
        """Start the profiler (no python tracer: its events would make
        most of the file) and open the ``bench.trace_window`` span the
        reduction takes the window from."""
        if self.trace_dir is None or self.tracing:
            return
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self.tracing = True
        self._window = jax.profiler.TraceAnnotation("bench.trace_window")
        self._window.__enter__()

    def stop_trace(self) -> None:
        if not self.tracing:
            return
        import jax

        self._window.__exit__(None, None, None)
        self._window = None
        self.tracing = False
        jax.profiler.stop_trace()


class GcWatch:
    """How long the interpreter's garbage collections stalled this
    process inside a ``with`` block, per generation: a pause hits the
    load generator and the server alike when they share a thread."""

    def __init__(self):
        self.pauses: Dict[int, List[float]] = {0: [], 1: [], 2: []}
        self._t0 = 0.0

    def _callback(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses[info["generation"]].append(
                time.perf_counter() - self._t0)

    def __enter__(self) -> "GcWatch":
        import gc

        gc.callbacks.append(self._callback)
        return self

    def __exit__(self, *exc) -> None:
        import gc

        gc.callbacks.remove(self._callback)

    def summary(self) -> Dict[str, Any]:
        return {f"gen{g}": {"n": len(p), "total_ms": sum(p) * 1e3,
                            "max_ms": max(p, default=0.0) * 1e3}
                for g, p in self.pauses.items()}


# --------------------------------------------------------------- device
def device_facts(memory_stats: Optional[List[dict]] = None,
                 scratch_bytes: int = 0) -> Dict[str, Any]:
    """The device as jax reports it, with the peak of the fullest chip:
    the allocator's peak of live buffers (from the path's reading right
    after the window when it took one) plus the scratch that the
    path's program holds on a chip while it runs, where the path read
    it (the runtime's counter leaves an executable's scratch out)."""
    import jax

    devices = jax.devices()
    if memory_stats is None:
        memory_stats = [d.memory_stats() or {} for d in devices]
    peaks = [s.get("peak_bytes_in_use", 0) for s in memory_stats]
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": int(max(peaks)) + int(scratch_bytes)}


def require_chips(chips: int) -> None:
    """Fail (no result line) unless jax's backend is an accelerator
    with at least the chips the cell asks for. No exception for a CPU
    asked for on purpose: a benchmark number comes from a chip."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise SystemExit(
            f"benchmark needs an accelerator, jax found "
            f"{devices[0].device_kind} x{len(devices)} (cpu)")
    if len(devices) < chips:
        raise SystemExit(f"cell needs {chips} chips, jax found "
                         f"{len(devices)}")


def start_backend(chips: int) -> str:
    """Place the compile cache, then fail unless the chips are there;
    returns the cache directory. Every program goes to the persistent
    cache, however short its compile: the sub-0.5 s ones add up to
    ~10 s on every warm start."""
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    from ddls_tpu.utils.runtime import configure_compile_cache

    cache_dir = configure_compile_cache()
    require_chips(chips)
    return cache_dir


def peaks_for(kind: str) -> dict:
    """Published peaks of the device kind; an unknown kind is an error,
    not a default."""
    table = read_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"benchmarks/peaks.json (has {sorted(table)})")
    return table[kind]


# ------------------------------------------------------ per-layer metrics
def read_layer_metric(name: str, ctx: Dict[str, Any],
                      root: str = BENCH_DIR) -> Optional[float]:
    """``layer_metrics/<name>.json`` names a kind of source and its
    parameters; ``sources/<kind>.py`` reads it from the run's context.
    ``None`` (nothing to read) leaves the metric out of the line."""
    spec = read_json(os.path.join(root, "layer_metrics", name + ".json"))
    source = spec["source"]
    reader = importlib.import_module(
        f"benchmarks.sources.{source['kind']}")
    value = reader.read(source, ctx)
    if value is None:
        return None
    return float(value) * float(spec.get("scale", 1.0))


def layer_metrics(cell: Cell, ctx: Dict[str, Any]) -> Dict[str, dict]:
    out = {}
    for metric in cell.per_layer:
        value = read_layer_metric(metric["name"], ctx)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


# ----------------------------------------------------------- result line
def result_line(correct: bool, attempted: int, failed: int,
                metrics: Dict[str, dict], device: Dict[str, Any],
                breakdown: Optional[dict] = None,
                compared: Optional[Dict[str, dict]] = None) -> str:
    """``compared``: what decided ``correct``, each number beside its
    limit, under a key of its own that comes last."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if compared is not None:
        line["compared"] = compared
    return json.dumps(line)


def compared_checks(checks: Dict[str, bool]) -> Dict[str, dict]:
    """Yes-or-no checks as numbers beside their limit: 1 where the
    check failed, and none may."""
    return {name: {"value": int(not ok), "limit": 0}
            for name, ok in checks.items()}


def note(tag: str, payload: Any) -> None:
    """An earlier line of output: facts beside the result."""
    print(f"[bench] {tag}: {json.dumps(payload, default=str)}", flush=True)
