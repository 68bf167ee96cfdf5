"""``stream_read_ratio.fwi``, the streamed kernel's window traffic over
the least a block must move, on hand-placed ``fwi.remesh`` and
``fwi.dispatch`` spans: at the 4096² and the padded Marmousi2 tiling,
weighted by steps across sessions, and None without its spans or on a
program older than the tiling attributes or the span module."""
import collections
import sys

import pytest

from bench import harness
from bench import trace as tr
from repro.core import spans
from repro.core.spans import Span

NAME = "stream_read_ratio.fwi"

#: the streamed kernel's tiling as the program reports it: the 4096²
#: grid unpadded, tiles of 4 and 16-row strips; the 13601 × 2801 survey
#: grid padded to 13696 × 2808, tiles of 1 and 8-row strips
TILINGS = {
    "4096": (dict(nz=4096, nx=4096, n_shots=16),
             dict(stripes=1, rows=4096, lanes=4096, stream=True,
                  shot_tile=4, bz=16, win=32)),
    "marmousi2": (dict(nz=2801, nx=13601, n_shots=12),
                  dict(stripes=1, rows=2808, lanes=13696, stream=True,
                       shot_tile=1, bz=8, win=24)),
}


def _run(fwi, ops=None, sessions=(), window=(100.0, 110.0)):
    """A run whose trace clock starts at the window's start."""
    return harness.Run(
        fwi=fwi, conf={}, mix={}, device_kind="TPU v5 lite", setup_s=0.0,
        window=window, sessions=list(sessions), transitions=[], spans=[],
        trace=tr.Trace(ops=ops or {0: []}, spans=[]),
        trace_window=(0.0, (window[1] - window[0]) * 1e9))


@pytest.fixture
def record(monkeypatch):
    """A fresh record of the program's spans, filled by hand."""
    fresh = collections.deque(maxlen=spans.MAX_SPANS)
    monkeypatch.setattr(spans, "RECORD", fresh)

    def add(name, t0, t1, id, parent=None, **attrs):
        fresh.append(Span(name, attrs, id=id, parent=parent, t0=t0, t1=t1))
    return add


def _read(run):
    return harness.metric_reader(NAME)(run)


@pytest.mark.parametrize("grid,ratio", [("4096", 1.697), ("marmousi2", 3.392)])
def test_stream_read_ratio_counts_the_tiled_window_traffic(record, grid,
                                                           ratio):
    fwi, tiling = TILINGS[grid]
    record("fwi.remesh", 100.1, 100.2, 1, session=1, **tiling)
    for i in range(3):
        record("fwi.dispatch", 101.0 + i, 101.1 + i, 2 + i, session=1,
               steps=8)
    assert _read(_run(fwi)) == pytest.approx(ratio, abs=1e-3)


def test_stream_read_ratio_weights_sessions_by_their_steps(record):
    # one stripe of 4096 lanes for 8 steps, then four of 1024 lanes, one
    # tile of 16 shots, for 24: each session's own ratio, weighted 1:3
    fwi, one = TILINGS["4096"]
    four = dict(one, stripes=4, lanes=1024, shot_tile=16)
    record("fwi.remesh", 100.1, 100.2, 1, session=1, **one)
    record("fwi.dispatch", 100.3, 100.4, 2, session=1, steps=8)
    record("fwi.remesh", 101.1, 101.2, 3, session=2, **four)
    for i in range(3):
        record("fwi.dispatch", 102.0 + i, 102.1 + i, 4 + i, session=2,
               steps=8)
    # (4S + 2) fields of the logical grid; per strip and tile,
    # (2s + 2) windows read and 2s strips written
    least = 66 * 4096 * 4096 * 4
    r_one = 4 * 256 * (10 * 32 + 8 * 16) * 4096 * 4 / least
    r_four = 4 * 1 * 256 * (34 * 32 + 32 * 16) * 1024 * 4 / least
    assert _read(_run(fwi)) == pytest.approx((r_one * 8 + r_four * 24) / 32)


def test_stream_read_ratio_is_none_without_the_tiling(record):
    """A program whose ``fwi.remesh`` carries no tiling (one older than
    the padded geometry) reads as None."""
    record("fwi.remesh", 100.1, 100.2, 1, session=1, stripes=1)
    record("fwi.dispatch", 101.0, 101.1, 2, session=1, steps=8)
    assert _read(_run(TILINGS["4096"][0])) is None


def _session():
    return harness.SessionRecord(stripes=1, devices=[0], created=100.0,
                                 t_begin=0, t_end=8, ended=110.0)


def test_stream_read_ratio_is_none_without_its_spans(record):
    ops = {0: [((101.0 - 100.0) * 1e9, (103.0 - 100.0) * 1e9, "copy.1")]}
    assert _read(_run(TILINGS["4096"][0], ops, [_session()])) is None


def test_stream_read_ratio_is_none_for_a_program_without_spans(monkeypatch):
    """The benchmark may run a program older than its span module: that
    reads as None, and raises nothing."""
    import repro.core
    monkeypatch.delattr(repro.core, "spans")
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    assert _read(_run(TILINGS["4096"][0], sessions=[_session()])) is None
