"""The benchmark's copy of the load generator against the program's,
and the benchmark's own latency arithmetic on hand-made responses."""
import numpy as np
import pytest

from benchmarks import loadgen
from ddls_tpu.serve import loadgen as program_loadgen

KNOBS = dict(diurnal_amplitude=0.0, burst_factor=1.0, size_tail_alpha=1.5,
             n_tenants=4)


@pytest.mark.parametrize("seed", [0, 7])
def test_copy_is_bit_equal_to_the_programs_generator(seed):
    ours = loadgen.generate_trace(512, 200.0, seed=seed)
    theirs = program_loadgen.generate_trace(512, 200.0, seed=seed)
    for key in ("arrival_s", "size_frac"):
        assert np.array_equal(ours[key], theirs[key])
    assert ours["tenant"] == theirs["tenant"]
    assert ours["meta"] == theirs["meta"]
    assert (loadgen.trace_fingerprint(ours)
            == program_loadgen.trace_fingerprint(theirs))


def test_fingerprint_is_pinned():
    trace = loadgen.generate_trace(512, 200.0, seed=7)
    assert loadgen.trace_fingerprint(trace) == "4851a037aaddb492"


def test_fixed_span_trace_offers_a_fixed_amount_of_work():
    a = loadgen.fixed_span_trace(10, 1000, seed=3, **KNOBS)
    b = loadgen.fixed_span_trace(10, 1000, seed=4, **KNOBS)
    assert len(a["arrival_s"]) == len(b["arrival_s"]) == 10_000
    assert 9.9 < a["arrival_s"][-1] < 10.0
    assert abs(a["span_scale"] - 1.0) < 0.05
    assert loadgen.trace_fingerprint(a) != loadgen.trace_fingerprint(b)
    again = loadgen.fixed_span_trace(10, 1000, seed=3, **KNOBS)
    assert loadgen.trace_fingerprint(a) == loadgen.trace_fingerprint(again)
    assert loadgen.fixed_span_trace(10, 1000, seed=3,
                                    **KNOBS)["meta"]["n_requests"] == 10_000


def test_percentile_charges_failures_the_largest_latency():
    # 98 fast answers, one slow, one failed: the failed one takes the
    # slow one's 10 ms, so two samples sit at the top
    summary = loadgen.latency_summary([0.001] * 98 + [0.010, None])
    assert summary["attempted"] == 100 and summary["failed"] == 1
    assert summary["p50_ms"] == pytest.approx(1.0)
    assert summary["pq_ms"] == pytest.approx(10.0)
    assert summary["max_ms"] == pytest.approx(10.0)
    assert summary["beyond_pq"] == 1
    clean = loadgen.latency_summary([0.001] * 99 + [0.010])
    assert clean["failed"] == 0 and clean["pq_ms"] < summary["pq_ms"]


def test_nothing_answered_gives_no_percentile():
    summary = loadgen.latency_summary([None, None])
    assert summary["failed"] == 2 and summary["pq_ms"] is None
