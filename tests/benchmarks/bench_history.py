"""``BENCHMARK.json`` as an earlier PR left it, for the tests of a cell
(``conftest.py``, ``test_bench_sala.py``): PRs only append."""
import copy


def benchmark_as_of(bench: dict, cell: str) -> dict:
    """``BENCHMARK.json`` without what was appended after ``cell``."""
    names = [w["name"] for w in bench["workloads"]]
    kept = names[:names.index(cell) + 1]
    out = copy.deepcopy(bench)
    out["workloads"] = [w for w in out["workloads"] if w["name"] in kept]
    configs = {w["config"] for w in out["workloads"]}
    out["configs"] = [c for c in out["configs"] if c["name"] in configs]
    for kind in ("end_to_end", "per_layer"):
        metrics = []
        for metric in out[kind]:
            if "workloads" in metric:
                metric["workloads"] = [w for w in metric["workloads"]
                                       if w in kept]
                if not metric["workloads"]:
                    continue        # a later cell's metric alone
            metrics.append(metric)
        out[kind] = metrics
    return out
