"""Trace reduction: interval arithmetic, and the reduction of a small
trace recorded on four TPU v5e chips (``record_fixture.py``): device
busy union, kernel events by name, collectives with no compute beside
them, and the benchmark's host spans."""
import json
import re
from pathlib import Path

import numpy as np
import pytest

from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"
KERNEL = re.compile(r"wave_block_shots")


def test_union_merges_overlaps_and_drops_empties():
    assert tr.union([(5, 7), (1, 3), (2, 4), (9, 9), (7, 8)]) == \
        [(1, 4), (5, 8)]


def test_intersect_and_subtract():
    xs = [(0, 10), (20, 30)]
    ys = [(5, 25), (28, 40)]
    assert tr.intersect(xs, ys) == [(5, 10), (20, 25), (28, 30)]
    assert tr.subtract(xs, ys) == [(0, 5), (25, 28)]
    assert tr.subtract(xs, []) == xs
    assert tr.total(tr.subtract(xs, ys)) + \
        tr.total(tr.intersect(xs, ys)) == tr.total(xs)


def _cover(intervals, lo, hi) -> np.ndarray:
    """Brute force: which of the nanoseconds in [lo, hi) are covered."""
    out = np.zeros(int(hi - lo), bool)
    for a, b in intervals:
        out[int(max(a, lo) - lo): int(max(min(b, hi), lo) - lo)] = True
    return out


def test_interval_ops_agree_with_brute_force():
    rng = np.random.default_rng(0)
    for _ in range(20):
        a = tr.union((s, s + d) for s, d in
                     rng.integers(0, 400, (30, 2)).tolist())
        b = tr.union((s, s + d) for s, d in
                     rng.integers(0, 400, (30, 2)).tolist())
        ca, cb = _cover(a, 0, 900), _cover(b, 0, 900)
        assert (_cover(tr.intersect(a, b), 0, 900) == (ca & cb)).all()
        assert (_cover(tr.subtract(a, b), 0, 900) == (ca & ~cb)).all()


@pytest.fixture(scope="module")
def recorded():
    return (tr.load(DATA / "burst4.xplane.pb"),
            json.loads((DATA / "burst4.json").read_text()))


def test_recorded_trace_has_four_devices_and_the_host_spans(recorded):
    trace, facts = recorded
    assert facts["device_kind"] == "TPU v5 lite"
    assert sorted(trace.ops) == [0, 1, 2, 3]
    names = {n for _, _, n in trace.spans}
    assert {"bench.window", "bench.factory", "bench.checkpoint",
            "bench.dispatch"} <= names
    (w0, w1), = trace.span("bench.window")
    factories = trace.span("bench.factory")
    assert len(factories) == len(facts["sessions"])
    assert all(w0 <= a <= b <= w1 for a, b in factories)
    assert len(trace.span("bench.checkpoint")) >= facts["transitions"] >= 2


def test_busy_union_agrees_with_brute_force(recorded):
    trace, _ = recorded
    (w0, w1), = trace.span("bench.window")
    lo = w0
    hi = min(w1, w0 + 2e6)                  # the first 2 ms, to the ns
    for d in trace.ops:
        evs = [(a, b) for a, b, _ in trace.ops[d]]
        busy = tr.intersect(trace.busy(d), [(lo, hi)])
        assert _cover(busy, lo, hi).sum() == _cover(evs, lo, hi).sum()
    assert tr.total(trace.busy(0)) > 0


def test_kernel_events_are_found_by_name(recorded):
    trace, facts = recorded
    # every device that held a stripe ran the shot-batched kernel
    used = {d for s in facts["sessions"] for d in s[1]}
    for d in used:
        assert any(KERNEL.search(n) for _, _, n in trace.ops[d]), d
    assert trace.op_seconds(KERNEL) > 0
    assert trace.op_seconds(KERNEL, devices={0}) < trace.op_seconds(KERNEL)


def test_collectives_without_compute(recorded):
    trace, _ = recorded
    for d in (1, 2):                       # interior stripes of 4
        comm = trace.busy(d, tr.COLLECTIVE)
        assert comm, d
        compute = tr.union((a, b) for a, b, n in trace.ops[d]
                           if not tr.COLLECTIVE.search(n))
        exposed = tr.subtract(comm, compute)
        assert 0 <= tr.total(exposed) <= tr.total(comm)
        for a, b in exposed:
            assert not any(x < b and y > a for x, y in compute)


def test_idle_is_attributed_to_host_spans(recorded):
    trace, _ = recorded
    (w0, w1), = trace.span("bench.window")
    held = {0: [(w0, w1)]}
    rows = tr.idle_by_span(trace, held)
    idle = tr.total(tr.subtract([(w0, w1)], trace.busy(0))) / 1e9
    assert sum(s for _, s in rows) == pytest.approx(idle, rel=1e-9)
    assert {n for n, _ in rows} <= {
        "orchestrator", "bench.dispatch", "bench.factory",
        "bench.checkpoint"}
