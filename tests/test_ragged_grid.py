"""Ragged grids on the normal FWI path: a width no stripe count divides
and a height no strip divides run padded (``stripe_geometry``), and
what leaves the session is on the logical grid.

The session is compared with the benchmark's plain reference
(``bench/reference.py``), which shares no code with the program, from
seeded plane-wave fields.  Tolerances, relative to max |reference|: the
XLA path computes the reference's float32 operations in its order, so
it may differ only by rounding (1e-6); the Pallas kernels sum the
stencil's z and x terms in another order (1e-5, the repository's
Pallas-against-reference tolerance).
"""
import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import reference, state  # noqa: E402
from repro.core import PodSpec, Resources  # noqa: E402
from repro.fwi.domain import stripe_geometry  # noqa: E402
from repro.fwi.driver import FWISession, TimeModel  # noqa: E402
from repro.fwi.solver import FWIConfig  # noqa: E402
import repro.kernels.stencil.kernel as kernel  # noqa: E402

#: 61 rows (prime) by 203 columns (7 · 29), 4 shots
FWI = dict(nz=61, nx=203, dt=5e-4, dx=5.0, timesteps=600, n_shots=4,
           sponge_width=8, sponge_strength=0.0125, source_freq=12.0,
           receiver_depth=2)
INIT = {"modes": 16, "wavelength_m": [125.0, 375.0], "speed_m_s": 2500.0,
        "amplitude": 1e-4}
TOL = {False: 1e-6, True: 1e-5}
#: a VMEM budget under which the 61 × 203 interior streams at shot tile
#: 1 with 8-row strips, as the 13601 × 2801 survey grid does under the
#: real one: the pickers' wide-row regime in miniature
SMALL_BUDGET = 300_000


def _fields(fwi, seed=7):
    return state.initial_fields(seed, shots=fwi["n_shots"], nz=fwi["nz"],
                                nx=fwi["nx"], init=INIT, dx=fwi["dx"],
                                dt=fwi["dt"])


def _session(fwi, restored, use_pallas, start_step=0):
    return FWISession(
        FWIConfig(**fwi), Resources(pods=[PodSpec(chips=1, name="cluster")],
                                    shares=[1.0]),
        start_step, restored, time_model=TimeModel(jitter=0.0),
        rng=np.random.default_rng(0), exchange_interval=4, scan_block=8,
        use_pallas=use_pallas)


def _gap(got, want) -> float:
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("stripes,rows,cols,tile,bz", [
    (1, 2808, 13696, 1, 8),
    (4, 2808, 13824, 6, 8),
])
def test_survey_grid_pads_to_what_the_streamed_kernel_tiles(
        stripes, rows, cols, tile, bz):
    cfg = FWIConfig(nz=2801, nx=13601, dt=7e-5, dx=1.25, n_shots=12)
    g = stripe_geometry(cfg, stripes, 4, True)
    assert (g.rows, g.cols, g.stripes) == (rows, cols, stripes)
    assert g.lanes % 128 == 0 and g.stream
    assert (g.shot_tile, g.bz, g.win) == (tile, bz, bz + 16)


@pytest.mark.parametrize("stripes", [1, 4])
def test_aligned_grid_is_not_padded(stripes):
    cfg = FWIConfig(nz=4096, nx=4096, dt=2e-4, n_shots=16)
    g = stripe_geometry(cfg, stripes, 4, True)
    assert not g.padded(cfg)
    assert (g.rows, g.lanes) == (4096, 4096 // stripes)


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("budget", [None, SMALL_BUDGET])
def test_ragged_session_matches_the_reference(monkeypatch, use_pallas,
                                              budget):
    fwi = dict(FWI)
    if budget is not None:
        # a configuration of its own, so that no cached runner built
        # under the real budget is reused
        monkeypatch.setattr(kernel, "DEFAULT_VMEM_BUDGET", budget)
        fwi["timesteps"] = 601
    p, pp = _fields(fwi)
    # placed from device arrays, then after a checkpoint from host ones
    first = _session(fwi, {"p": p, "p_prev": pp, "t": 0}, use_pallas)
    for step in range(8):
        first.run_step(step)
    snap = first.checkpoint(8)
    assert snap["p"].shape == snap["p_prev"].shape == p.shape
    s = _session(fwi, snap, use_pallas, start_step=8)
    for step in range(8, 16):
        s.run_step(step)
    assert s.t == 16
    rows, cols = s.carry[0].shape[-2:]
    if budget is not None:
        assert rows > fwi["nz"] and cols == 256
    else:
        assert (rows, cols) == (fwi["nz"], fwi["nx"])
    ref = reference.propagate(fwi, p, pp, np.arange(fwi["n_shots"]), 0, 16)
    for got, want in zip((s.p, s.p_prev), ref):
        assert got.shape == want.shape
        assert _gap(got, want) <= TOL[use_pallas]
    # the padded cells hold exactly 0 after a block
    for f in s.carry:
        f = np.asarray(f)
        assert not f[:, fwi["nz"]:, :].any()
        assert not f[:, :, fwi["nx"]:].any()


_STRIPES = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path[:0] = sys.argv[1:3]
import jax, numpy as np
from bench import state
from repro.core import (BurstPlanner, DeadlinePredictor, ElasticOrchestrator,
                        LogCapacityModel, OverheadModel, PodSpec, Resources,
                        ScaleAction)
from repro.fwi.driver import TimeModel, elastic_stripes_for, \
    fwi_session_factory
from repro.fwi.solver import FWIConfig

assert len(jax.devices()) == 4
fwi = dict(nz=61, nx=203, dt=5e-4, dx=5.0, timesteps=600, n_shots=4,
           sponge_width=8, sponge_strength=0.0125, source_freq=12.0,
           receiver_depth=2)
cfg = FWIConfig(**fwi)
init = {"modes": 16, "wavelength_m": [125.0, 375.0], "speed_m_s": 2500.0,
        "amplitude": 1e-4}
p0, pp0 = state.initial_fields(3, shots=4, nz=61, nx=203, init=init,
                               dx=5.0, dt=5e-4)
LEGAL = [16, 32, 64]
model = LogCapacityModel.fit(LEGAL, [64.0 / c for c in LEGAL])
planner = BurstPlanner(
    cluster_model=model, cloud_model=model, chips_cluster=64,
    legal_slices=LEGAL,
    overheads=OverheadModel(ckpt_s=5, provision_s=10, restart_s=5))


class Scripted:
    name = "scripted"
    script = {16: ScaleAction("grow", chips=3, slowdown=1.0),
              48: ScaleAction("retire")}

    def decide(self, ctx):
        return self.script.get(ctx.step, ScaleAction("hold"))


def job(autoscaler):
    sessions = []
    base = fwi_session_factory(
        cfg, TimeModel(chip_seconds_per_step=1.0, jitter=0.0),
        stripes_for=elastic_stripes_for(1, 4), exchange_interval=4,
        scan_block=8, use_pallas=False)

    def factory(res, start_step, restored):
        if restored is None:
            restored = {"p": p0, "p_prev": pp0, "t": 0}
        sessions.append(base(res, start_step, restored))
        return sessions[-1]

    ElasticOrchestrator(
        planner=planner, predictor=DeadlinePredictor(1e12), check_every=8,
        ckpt_every=10 ** 9,
    ).run(session_factory=factory,
          initial=Resources(pods=[PodSpec(chips=1, name="cluster")],
                            shares=[1.0]),
          steps_total=64, autoscaler=autoscaler)
    return sessions

elastic = job(Scripted())
flat = job(None)
print("stripes", [s.mesh.devices.size for s in elastic])
print("widths", [s.carry[0].shape[-1] for s in elastic])
print("shards", [len({sh.device for sh in s.carry[0].addressable_shards})
                 for s in elastic])
a, b = elastic[-1], flat[-1]
print("steps", a.t, b.t)
print("gap", max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
                 for x, y in ((a.p, b.p), (a.p_prev, b.p_prev))))
print("scale", float(np.max(np.abs(np.asarray(b.p)))))
"""


def test_ragged_width_runs_four_stripes_and_an_elastic_run_matches():
    """203 columns, which 4 does not divide, on 4 host devices: the GROW
    runs 4 stripes of 51 columns, not 1, and the 1 → 4 → 1 run ends
    where the unscaled one does (the sharded XLA path is bitwise equal
    up to denormal noise, so 1e-6 of max |p| is generous)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _STRIPES, str(ROOT / "src"), str(ROOT)],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    got = dict(line.split(" ", 1) for line in out.stdout.splitlines())
    assert got["stripes"] == "[1, 4, 1]", out.stdout
    assert got["widths"] == "[203, 204, 203]", out.stdout
    assert got["shards"] == "[1, 4, 1]", out.stdout
    assert got["steps"] == "64 64", out.stdout
    assert float(got["gap"]) <= 1e-6 * float(got["scale"]), out.stdout
