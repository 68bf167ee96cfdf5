"""The program's spans (``repro.core.spans``): the record itself, the
orchestrator's decide and transition spans, the FWI session's spans, and
the named scopes of the sharded runner's phases in its compiled HLO.

The record is one per process, and other tests in the same process
record into it too, so each test reads only the spans that ended after
its own start.
"""
import math
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro.core import (
    BurstPlanner,
    DeadlinePredictor,
    ElasticOrchestrator,
    LogCapacityModel,
    OverheadModel,
    PodSpec,
    Resources,
    ScaleAction,
    spans,
)
from repro.core.sim_session import SimWorkload, sim_session_factory
from repro.core.spans import span

SRC = str(Path(__file__).resolve().parents[1] / "src")
LEGAL = [16, 32, 64, 128, 256]


def since(t0: float, prefix: str = "") -> list:
    return [s for s in spans.recorded(t0, math.inf)
            if s.name.startswith(prefix)]


# ------------------------------------------------------------- record


def test_span_records_ids_times_and_attrs():
    t = time.perf_counter()
    with span("test.a", bytes=12, session=3) as a:
        pass
    with span("test.b") as b:
        pass
    got = since(t, "test.")
    assert got == [a, b]
    assert b.id > a.id > 0
    assert t <= a.t0 <= a.t1 <= b.t0 <= b.t1
    assert a.attrs == {"bytes": 12, "session": 3} and b.attrs == {}
    assert a.parent is None and b.parent is None


def test_parents_nest_across_three_levels():
    t = time.perf_counter()
    with span("test.outer") as outer:
        with span("test.middle") as middle:
            with span("test.inner") as inner:
                pass
            with span("test.sibling") as sibling:
                pass
    assert (outer.parent, middle.parent, inner.parent, sibling.parent) \
        == (None, outer.id, middle.id, middle.id)
    # recorded in the order they ended
    assert since(t, "test.") == [inner, sibling, middle, outer]
    assert outer.t0 <= middle.t0 <= inner.t0 <= inner.t1 <= middle.t1 \
        <= outer.t1


def test_attrs_can_be_added_inside_the_span():
    with span("test.place", session=1) as s:
        s.attrs["bytes"] = 64
    assert s.attrs == {"session": 1, "bytes": 64}


def test_record_is_bounded_and_drops_the_oldest():
    assert spans.RECORD.maxlen == spans.MAX_SPANS
    t = time.perf_counter()
    made = []
    for i in range(spans.MAX_SPANS + 5):
        with span("test.bound", i=i) as s:
            pass
        made.append(s)
    assert len(spans.RECORD) == spans.MAX_SPANS
    kept = since(t, "test.bound")
    assert kept == made[5:]


def test_recorded_keeps_only_spans_inside_the_interval():
    with span("test.early") as early:
        time.sleep(0.002)
    with span("test.middle") as middle:
        time.sleep(0.002)
    with span("test.late") as late:
        pass
    got = spans.recorded(middle.t0, middle.t1)
    assert middle in got and early not in got and late not in got
    # a span that starts before the interval does not lie within it
    assert early not in spans.recorded(early.t0 + 1e-9, late.t1)
    assert spans.recorded(late.t1 + 1.0, late.t1 + 2.0) == []


def test_a_span_is_recorded_while_an_exception_passes_through():
    t = time.perf_counter()
    with pytest.raises(KeyError):
        with span("test.outer") as outer:
            with span("test.failing") as failing:
                raise KeyError("x")
    assert since(t, "test.") == [failing, outer]
    assert failing.parent == outer.id and failing.t0 <= failing.t1
    # the stack of open spans was unwound: a new span has no parent
    with span("test.after") as after:
        pass
    assert after.parent is None


# ------------------------------------------------------- orchestrator


def _planner(chips=256):
    m = LogCapacityModel.fit(LEGAL, [2000.0 / c for c in LEGAL])
    return BurstPlanner(
        cluster_model=m, cloud_model=m, chips_cluster=chips,
        legal_slices=LEGAL,
        overheads=OverheadModel(ckpt_s=5, provision_s=60, restart_s=20),
    )


class _Scripted:
    name = "scripted"
    script = {16: ScaleAction("grow", chips=64, slowdown=1.4),
              32: ScaleAction("shrink", chips=32),
              48: ScaleAction("retire")}

    def decide(self, ctx):
        return self.script.get(ctx.step, ScaleAction("hold"))


class _Stub:
    """A session whose every step takes a second."""

    def run_step(self, step):
        return 1.0

    def checkpoint(self, step):
        return {"step": step}


class _Logged:
    """``inner``, whose checkpoints log the id of the span open around
    them."""

    def __init__(self, inner, log: list):
        self.inner = inner
        self.log = log

    def run_step(self, step):
        return self.inner.run_step(step)

    def checkpoint(self, step):
        with span("test.checkpoint") as s:
            self.log.append(("checkpoint", step, s.parent))
        return self.inner.checkpoint(step)


def _logged(factory, log: list):
    """``factory`` whose calls, and whose sessions' checkpoints, log the
    id of the span open around them."""

    def make(res, start_step, restored):
        with span("test.factory") as s:
            log.append(("factory", start_step, s.parent))
        return _Logged(factory(res, start_step, restored), log)

    return make


def test_orchestrator_spans_each_decision_and_transition():
    log: list = []
    orch = ElasticOrchestrator(
        planner=_planner(), predictor=DeadlinePredictor(10_000.0),
        check_every=8, ckpt_every=1000,
    )
    t = time.perf_counter()
    orch.run(session_factory=_logged(lambda *_: _Stub(), log),
             initial=Resources(pods=[PodSpec(256, name="cluster")],
                               shares=[1.0]),
             steps_total=64, autoscaler=_Scripted())
    decides = since(t, "orch.decide")
    assert [s.attrs["step"] for s in decides] == [8, 16, 24, 32, 40, 48, 56]
    transitions = since(t, "orch.transition")
    assert [(s.attrs["kind"], s.attrs["step"]) for s in transitions] == \
        [("grow", 16), ("shrink", 32), ("retire", 48)]
    ids = {s.attrs["step"]: s.id for s in transitions}
    # the initial session is made outside any span; each transition's
    # checkpoint and session factory run inside its span
    assert log[0] == ("factory", 0, None)
    assert log[1:] == [(what, step, ids[step]) for step in (16, 32, 48)
                       for what in ("checkpoint", "factory")]
    at = {d.attrs["step"]: d for d in decides}
    for s in transitions:
        assert at[s.attrs["step"]].t1 <= s.t0
        assert at[s.attrs["step"]].parent is None


def test_orchestrator_spans_a_planner_burst():
    log: list = []
    orch = ElasticOrchestrator(
        planner=_planner(), predictor=DeadlinePredictor(10_000.0),
        check_every=8,
    )
    t = time.perf_counter()
    rec = orch.run(
        session_factory=_logged(sim_session_factory(
            SimWorkload(2000.0, jitter=0.01),
            rng=np.random.default_rng(1)), log),
        initial=Resources(pods=[PodSpec(256, name="cluster")],
                          shares=[1.0]),
        steps_total=300, deadline_changes=[(450.0, 1800.0)],
    )
    moves = [(e.kind, e.step) for e in rec.events
             if e.kind in ("burst", "rebalance")]
    assert moves[0][0] == "burst"
    transitions = since(t, "orch.transition")
    assert [(s.attrs["kind"], s.attrs["step"]) for s in transitions] == moves
    steps = [s.attrs["step"] for s in since(t, "orch.decide")]
    assert steps == list(range(8, 300, 8))
    assert [x for x in log if x[2] is not None] == [
        (what, s.attrs["step"], s.id) for s in transitions
        for what in ("checkpoint", "factory")]


# -------------------------------------------------------- FWI session


def _fwi_session(restored=None, start_step=0):
    from repro.fwi.driver import FWISession, TimeModel
    from repro.fwi.solver import FWIConfig

    cfg = FWIConfig(nz=32, nx=64, timesteps=32, n_shots=2, sponge_width=4)
    return FWISession(
        cfg, Resources(pods=[PodSpec(chips=1, name="cluster")],
                       shares=[1.0]),
        start_step, restored, time_model=TimeModel(jitter=0.0),
        rng=np.random.default_rng(0), exchange_interval=4, scan_block=8)


def _of(session, t0):
    return [s for s in since(t0, "fwi.")
            if s.attrs.get("session") == session.session
            or s.name == "fwi.fetch"]


def test_fwi_session_spans_its_mesh_placement_blocks_and_checkpoint():
    t = time.perf_counter()
    s = _fwi_session()
    s.run_step(0)
    snap = s.checkpoint(1)
    got = _of(s, t)
    assert [x.name for x in got] == [
        "fwi.remesh", "fwi.place", "fwi.dispatch", "fwi.wait",
        "fwi.fetch", "fwi.fetch", "fwi.checkpoint"]
    remesh, place, dispatch, wait, f_p, f_pp, ckpt = got
    # an aligned grid on the XLA path: no padding, one unstripped block,
    # one stripe's single window
    assert remesh.attrs == {
        "session": s.session, "stripes": 1, "rows": 32, "lanes": 64,
        "stream": False, "shot_tile": 2, "bz": None, "win": None,
        "schedule": "single"}
    assert place.attrs == {
        "session": s.session, "bytes": s.p.nbytes + s.p_prev.nbytes,
        "padded_bytes": s.p.nbytes + s.p_prev.nbytes,
        "devices": [d.id for d in s.mesh.devices.flat]}
    assert dispatch.attrs == {"session": s.session, "steps": 8}
    assert dispatch.t1 <= wait.t0
    assert ckpt.attrs == {"session": s.session, "stripes": 1}
    assert (f_p.parent, f_pp.parent) == (ckpt.id, ckpt.id)
    assert (f_p.attrs["bytes"], f_pp.attrs["bytes"]) == \
        (snap["p"].nbytes, snap["p_prev"].nbytes) == \
        (s.p.nbytes, s.p_prev.nbytes)
    # the amortised step time is the recorded dispatch-and-wait pair's
    assert s._amortized == (wait.t1 - dispatch.t0) / 8


def test_fwi_session_spans_its_padded_geometry(monkeypatch):
    """61 × 203 under a small VMEM budget streams as the 13601 × 2801
    survey grid does under the real one: shot tile 1, 8-row strips, the
    height padded to 64 and the width to 256 lanes."""
    import repro.kernels.stencil.kernel as kernel
    from repro.fwi.driver import FWISession, TimeModel
    from repro.fwi.solver import FWIConfig

    monkeypatch.setattr(kernel, "DEFAULT_VMEM_BUDGET", 300_000)
    cfg = FWIConfig(nz=61, nx=203, timesteps=33, n_shots=2, sponge_width=4)
    t = time.perf_counter()
    s = FWISession(
        cfg, Resources(pods=[PodSpec(chips=1, name="cluster")],
                       shares=[1.0]),
        0, None, time_model=TimeModel(jitter=0.0),
        rng=np.random.default_rng(0), exchange_interval=4, scan_block=8,
        use_pallas=True)
    remesh, place = _of(s, t)
    assert remesh.attrs == {
        "session": s.session, "stripes": 1, "rows": 64, "lanes": 256,
        "stream": True, "shot_tile": 1, "bz": 8, "win": 24,
        "schedule": "single"}
    assert place.attrs["bytes"] == 2 * 2 * 61 * 203 * 4
    assert place.attrs["padded_bytes"] == 2 * 2 * 64 * 256 * 4
    assert s.p.shape == (2, 61, 203)


def test_fwi_session_restored_in_a_transition_spans_its_placement():
    first = _fwi_session()
    first.run_step(0)
    snap = first.checkpoint(1)
    t = time.perf_counter()
    with span("orch.transition", kind="grow", step=1) as transition:
        second = _fwi_session(snap, start_step=1)
    remesh, place = _of(second, t)
    assert second.session != first.session
    assert (remesh.name, place.name) == ("fwi.remesh", "fwi.place")
    assert remesh.parent == place.parent == transition.id
    assert place.attrs["bytes"] == snap["p"].nbytes + snap["p_prev"].nbytes


_TWO_STRIPE_SESSION = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, sys.argv[1])
import time
import numpy as np
from repro.core import PodSpec, Resources, spans
from repro.fwi.domain import pick_schedule
from repro.fwi.driver import FWISession, TimeModel
from repro.fwi.solver import FWIConfig

t = time.perf_counter()
cfg = FWIConfig(nz=32, nx=64, timesteps=32, n_shots=2, sponge_width=4)
s = FWISession(
    cfg, Resources(pods=[PodSpec(chips=2, name="cluster")], shares=[1.0]),
    0, None, time_model=TimeModel(jitter=0.0),
    rng=np.random.default_rng(0), n_stripes=2, exchange_interval=4)
remesh, = [x for x in spans.recorded(t, float("inf"))
           if x.name == "fwi.remesh"]
print("stripes", remesh.attrs["stripes"])
print("schedule", remesh.attrs["schedule"], pick_schedule(2))
"""


def test_fwi_remesh_names_the_schedule_it_resolved():
    """One stripe's session resolves the single window; a two-stripe
    session resolves the backend's exchanging schedule, and its
    ``fwi.remesh`` span says so."""
    t = time.perf_counter()
    _fwi_session()
    remesh = [x for x in since(t, "fwi.") if x.name == "fwi.remesh"][-1]
    assert remesh.attrs["schedule"] == "single"

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _TWO_STRIPE_SESSION, SRC],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.split("\n")
    assert "stripes 2" in lines, out.stdout
    assert "schedule fused fused" in lines, out.stdout


# -------------------------------------------------- scopes in the HLO


_SCOPES = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from repro.fwi.domain import make_sharded_scan_runner, stripe_mesh
from repro.fwi.solver import FWIConfig

cfg = FWIConfig(nz=32, nx=64, timesteps=32, n_shots=2, sponge_width=4)
assert len(jax.devices()) == 2
for schedule in ("overlap", "pipeline"):
    run, place, k = make_sharded_scan_runner(
        cfg, stripe_mesh(2), k=4, use_pallas=False, overlap=schedule)
    z = jnp.zeros((cfg.n_shots, cfg.nz, cfg.nx), jnp.float32)
    p, pp = place((z, z))
    hlo = run.lower(p, pp, 0, blocks=2).compile().as_text()
    print(schedule, "resolved", place.schedule)
    for scope in ("fwi.exchange", "fwi.boundary", "fwi.interior",
                  "fwi.stitch", "fwi.traces"):
        print(schedule, scope, ('/' + scope + '/') in hlo)
"""


def test_sharded_runner_phases_carry_named_scopes_in_the_hlo():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SCOPES, SRC],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.split("\n")
    for schedule in ("overlap", "pipeline"):
        assert f"{schedule} resolved {schedule}" in lines, out.stdout
        for scope in ("fwi.exchange", "fwi.boundary", "fwi.interior",
                      "fwi.stitch", "fwi.traces"):
            assert f"{schedule} {scope} True" in lines, out.stdout


def test_shot_tiles_carry_named_scopes_in_the_hlo():
    import jax
    import jax.numpy as jnp

    from repro.kernels.stencil.ops import wave_block

    s, nz, nx, k = 4, 16, 32, 4
    f = jnp.zeros((s, nz, nx), jnp.float32)
    m = jnp.ones((nz, nx), jnp.float32)
    at = jnp.zeros((s,), jnp.int32)
    hlo = jax.jit(lambda p, pp: wave_block(
        p, pp, 0.1 * m, m, jnp.ones((k,)), at + 8, at + 16,
        use_pallas=False, shot_tile=2)).lower(f, f).compile().as_text()
    assert "/stencil.tile_split/" in hlo
    assert "/stencil.tile_concat/" in hlo


@pytest.mark.parametrize("nz,nx,padded", [(32, 64, False), (61, 203, True)])
def test_padding_carries_named_scopes_only_on_a_ragged_grid(
        monkeypatch, nz, nx, padded):
    """Placement, a scan and the crop in one program: a ragged grid
    pads under ``fwi.pad`` and crops under ``fwi.crop``; an aligned one
    compiles with neither."""
    import jax
    import jax.numpy as jnp

    import repro.kernels.stencil.kernel as kernel
    from repro.fwi.domain import crop, make_sharded_scan_runner, stripe_mesh
    from repro.fwi.solver import FWIConfig

    # the 61 × 203 interior streams under this budget, so it is padded
    monkeypatch.setattr(kernel, "DEFAULT_VMEM_BUDGET", 300_000)
    cfg = FWIConfig(nz=nz, nx=nx, timesteps=34, n_shots=2, sponge_width=4)
    run, place, k = make_sharded_scan_runner(
        cfg, stripe_mesh(1), k=4, use_pallas=False, overlap="overlap")
    assert place.geometry.padded(cfg) == padded

    def program(p, pp):
        pn, pd, traces = run(*place((p, pp)), 0, 2)
        return crop(cfg, pn), crop(cfg, pd), traces

    z = jnp.zeros((cfg.n_shots, nz, nx), jnp.float32)
    hlo = jax.jit(program).lower(z, z).compile().as_text()
    for scope in ("fwi.pad", "fwi.crop"):
        assert (f"/{scope}/" in hlo) == padded, scope
