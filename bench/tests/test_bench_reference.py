"""The benchmark's plain reference against the program's XLA path
(``use_pallas=False``) on the CPU at a small grid, over several blocks:
on one stripe here, and on two stripes in a subprocess with two
virtual CPU devices."""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np

from bench import reference, state

ROOT = Path(__file__).resolve().parents[2]
FWI = dict(nz=48, nx=64, dt=5e-4, dx=5.0, timesteps=600, n_shots=4,
           sponge_width=8, sponge_strength=0.0125, source_freq=12.0,
           receiver_depth=2)
INIT = {"modes": 8, "wavelength_m": [125.0, 375.0], "speed_m_s": 2500.0,
        "amplitude": 1e-4}
STEPS = 96          # 24 blocks of 4, through the source's firing


def rel(a, b) -> float:
    a, b = np.asarray(a), np.asarray(b)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def test_model_fields_match_the_programs():
    from repro.fwi.solver import FWIConfig, sponge_taper, velocity_model

    cfg = FWIConfig(**FWI)
    assert np.array_equal(reference.velocity(FWI),
                          np.asarray(velocity_model(cfg)))
    assert np.array_equal(reference.sponge(FWI),
                          np.asarray(sponge_taper(cfg)))
    z, x = reference.sources(FWI)
    assert np.array_equal(np.stack([z, x], 1), cfg.shot_positions())


def test_reference_matches_the_xla_path_on_one_stripe():
    from repro.fwi.solver import FWIConfig, ShotState, run_forward

    cfg = FWIConfig(**FWI)
    p, pp = state.initial_fields(7, shots=4, nz=48, nx=64, init=INIT,
                                 dx=5.0, dt=5e-4)
    st, _ = run_forward(cfg, use_pallas=False, k=4, steps=STEPS,
                        state=ShotState(p=p, p_prev=pp, t=0))
    shots = np.array([1, 3])
    rp, rpp = reference.propagate(FWI, p[shots], pp[shots], shots, 0,
                                  STEPS)
    assert rel(rp, st.p[shots]) <= 1e-6
    assert rel(rpp, st.p_prev[shots]) <= 1e-6
    # a reference that does nothing would not pass
    assert rel(p[shots], st.p[shots]) > 1e-2


_TWO_STRIPES = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
sys.path[:0] = sys.argv[1:3]
import jax, numpy as np
from bench import reference, state
from repro.fwi.domain import make_sharded_scan_runner, stripe_mesh
from repro.fwi.solver import FWIConfig
FWI = dict(nz=48, nx=64, dt=5e-4, dx=5.0, timesteps=600, n_shots=4,
           sponge_width=8, sponge_strength=0.0125, source_freq=12.0,
           receiver_depth=2)
INIT = {"modes": 8, "wavelength_m": [125.0, 375.0], "speed_m_s": 2500.0,
        "amplitude": 1e-4}
cfg = FWIConfig(**FWI)
p, pp = state.initial_fields(9, shots=4, nz=48, nx=64, init=INIT,
                             dx=5.0, dt=5e-4)
mesh = stripe_mesh(2)
run, place, k = make_sharded_scan_runner(cfg, mesh, k=4, use_pallas=False)
a, b = place((p, pp))
a, b, _ = run(a, b, 0, 24)
assert len({s.device for s in a.addressable_shards}) == 2
shots = np.arange(4)
rp, rpp = reference.propagate(FWI, p, pp, shots, 0, 96,
                              devices=jax.devices()[:2])
for got, want in ((a, rp), (b, rpp)):
    got, want = np.asarray(got), np.asarray(want)
    print(float(np.max(np.abs(got - want)) / np.max(np.abs(want))))
"""


def test_reference_matches_the_xla_path_on_two_stripes():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _TWO_STRIPES, str(ROOT / "src"), str(ROOT)],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    gaps = [float(x) for x in out.stdout.split()]
    assert len(gaps) == 2 and max(gaps) <= 1e-6, gaps
