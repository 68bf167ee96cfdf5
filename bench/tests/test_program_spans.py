"""The metrics that read the program's own spans: the program's spans and
the profiler's annotations share one clock once mapped through the
window span, and each reader on a run with hand-placed spans and device
operations, and without its spans."""
import collections
import sys
import time

import jax
import pytest
from jax.profiler import ProfileData

from bench import harness
from bench import trace as tr
from repro.core import spans
from repro.core.spans import Span

NEW = ["decide_ms.fwi", "dispatch_ms.fwi", "fetch_gbps.burst",
       "restore_idle_s.burst", "idle_unspanned.fwi"]


def test_program_spans_share_the_trace_clock(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    rec = harness.Recorder(True)
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with rec.span("window"):
        with spans.span("fwi.outer") as outer:
            time.sleep(0.01)
            with spans.span("fwi.inner") as inner:
                time.sleep(0.005)
        time.sleep(0.003)
        with spans.span("orch.after") as after:
            pass
    jax.profiler.stop_trace()
    xplane = next(tmp_path.rglob("*.xplane.pb"))
    trace = tr.load(xplane)
    (_, w0, w1), = rec.spans
    run = _run({}, [], window=(w0, w1))
    run.trace_window, = trace.span(tr.SPAN_PREFIX + "window")
    annotated = {}
    for plane in ProfileData.from_file(str(xplane)).planes:
        if plane.name == tr.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    annotated[e.name] = (e.start_ns, e.end_ns)
    for s in (outer, inner, after):
        a, b = annotated[s.name]
        assert abs(run.to_trace(s.t0) - a) < 1e6, s.name
        assert abs(run.to_trace(s.t1) - b) < 1e6, s.name


# ------------------------------------------------------------ readers


def _run(ops, sessions, window=(100.0, 110.0)):
    """A run whose trace clock starts at the window's start: host time
    ``t`` is ``(t - window[0]) * 1e9`` ns there."""
    return harness.Run(
        fwi={}, conf={}, mix={}, device_kind="TPU v5 lite", setup_s=0.0,
        window=window, sessions=sessions, transitions=[], spans=[],
        trace=tr.Trace(ops=ops, spans=[]),
        trace_window=(0.0, (window[1] - window[0]) * 1e9))


def _ns(*host_s):
    return [(t - 100.0) * 1e9 for t in host_s]


@pytest.fixture
def record(monkeypatch):
    """A fresh record of the program's spans, filled by hand."""
    fresh = collections.deque(maxlen=spans.MAX_SPANS)
    monkeypatch.setattr(spans, "RECORD", fresh)

    def add(name, t0, t1, id, parent=None, **attrs):
        fresh.append(Span(name, attrs, id=id, parent=parent, t0=t0, t1=t1))
    return add


def _burst(add):
    """One block on one stripe, then a GROW onto four: the checkpoint
    fetches 2 GB in a second, the new session's first block ends 1 s
    after its placement began, 0.3 s of it busy on each device."""
    add("orch.decide", 100.5, 100.5001, 1, step=8)
    add("fwi.dispatch", 100.6, 100.602, 2, session=1, steps=8)
    add("fwi.wait", 100.602, 100.7, 3, session=1)
    add("orch.decide", 100.99, 100.9903, 4, step=16)
    add("fwi.fetch", 101.0, 101.5, 12, parent=11, bytes=10 ** 9)
    add("fwi.fetch", 101.5, 102.0, 13, parent=11, bytes=10 ** 9)
    add("fwi.checkpoint", 101.0, 102.0, 11, parent=10, session=1,
        stripes=1)
    add("fwi.remesh", 102.0, 102.1, 14, parent=10, session=2, stripes=4)
    add("fwi.place", 102.1, 102.5, 15, parent=10, session=2,
        bytes=2 * 10 ** 9, devices=[0, 1, 2, 3])
    add("orch.transition", 101.0, 102.5, 10, kind="grow", step=16)
    add("fwi.dispatch", 102.6, 102.61, 16, session=2, steps=8)
    add("fwi.wait", 102.61, 103.1, 17, session=2)
    add("fwi.dispatch", 103.2, 103.21, 18, session=2, steps=8)
    add("fwi.wait", 103.21, 103.5, 19, session=2)
    # outside the window: read by no metric
    add("fwi.fetch", 111.0, 111.1, 20, bytes=10 ** 9)
    sessions = [
        harness.SessionRecord(stripes=1, devices=[0], created=100.0,
                              t_begin=0, t_end=8, ended=102.0),
        harness.SessionRecord(stripes=4, devices=[0, 1, 2, 3],
                              created=102.0, t_begin=8, t_end=24,
                              ended=110.0)]
    ops = {d: [(*_ns(102.8, 103.1), "wave_block_shots_stream_pallas.1")]
           for d in range(4)}
    ops[0].insert(0, (*_ns(100.61, 100.7), "wave_block_shots_stream_pallas.2"))
    return _run(ops, sessions)


def test_host_clock_readers(record):
    run = _burst(record)
    assert harness.metric_reader("decide_ms.fwi")(run) == \
        pytest.approx(1e3 * (0.0001 + 0.0003) / 2)
    assert harness.metric_reader("dispatch_ms.fwi")(run) == \
        pytest.approx(1e3 * (0.002 + 0.01 + 0.01) / 3)
    assert harness.metric_reader("fetch_gbps.burst")(run) == \
        pytest.approx(2.0)


def test_restore_idle_reads_the_new_mesh_until_its_first_block(record):
    run = _burst(record)
    # 1.0 s from the placement's start to the first wait's end, 0.3 s
    # of it busy on each of the four devices
    assert harness.metric_reader("restore_idle_s.burst")(run) == \
        pytest.approx(0.7)


def test_restore_idle_takes_only_placements_in_a_transition(record):
    record("fwi.place", 100.0, 100.1, 1, session=1, bytes=8, devices=[0])
    record("fwi.wait", 100.2, 100.3, 2, session=1)
    run = _run({0: []}, [])
    assert harness.metric_reader("restore_idle_s.burst")(run) is None


def test_idle_unspanned_counts_idle_outside_the_program_spans(record):
    # held 10 s; busy 101-103; the program's spans cover 100-101 and
    # 103-105, another span 105-106: 5 s of idle no program span names
    record("fwi.dispatch", 100.0, 101.0, 1, session=1, steps=8)
    record("orch.decide", 103.0, 105.0, 2, step=8)
    record("bench.other", 105.0, 106.0, 3)
    run = _run({0: [(*_ns(101.0, 103.0), "copy.1")]}, [
        harness.SessionRecord(stripes=1, devices=[0], created=100.0,
                              t_begin=0, t_end=8, ended=110.0)])
    assert harness.metric_reader("idle_unspanned.fwi")(run) == \
        pytest.approx(50.0)
    assert harness.metric_reader("idle_share.fwi")(run) == \
        pytest.approx(80.0)


def test_idle_unspanned_is_at_most_idle_share(record):
    run = _burst(record)
    unspanned = harness.metric_reader("idle_unspanned.fwi")(run)
    assert 0.0 < unspanned < harness.metric_reader("idle_share.fwi")(run)


@pytest.mark.parametrize("name", NEW)
def test_reader_is_none_without_its_spans(record, name):
    run = _run({0: [(*_ns(101.0, 103.0), "copy.1")]}, [
        harness.SessionRecord(stripes=1, devices=[0], created=100.0,
                              t_begin=0, t_end=8, ended=110.0)])
    assert harness.metric_reader(name)(run) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_is_none_for_a_program_without_spans(monkeypatch, name):
    """The benchmark may run a program older than its span module: that
    reads as None, and raises nothing."""
    import repro.core
    monkeypatch.delattr(repro.core, "spans")
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    run = _run({0: []}, [
        harness.SessionRecord(stripes=1, devices=[0], created=100.0,
                              t_begin=0, t_end=8, ended=110.0)])
    assert harness.metric_reader(name)(run) is None
