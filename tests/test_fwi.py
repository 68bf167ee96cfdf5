"""FWI solver: physics sanity, path equivalence, multi-stripe halo
exchange (subprocess with 4 host devices), checkpoint/re-stripe."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fwi.domain import (
    halo_bytes_per_step,
    make_sharded_scan_runner,
    stripe_mesh,
)
from repro.fwi.solver import (
    FWIConfig,
    ShotState,
    run_forward,
    sponge_taper,
    velocity_model,
)

CFG = FWIConfig(nz=128, nx=128, timesteps=60, n_shots=2, sponge_width=16)


def test_wavefield_nontrivial_and_finite():
    st, traces = run_forward(CFG)
    assert bool(jnp.all(jnp.isfinite(st.p)))
    assert float(jnp.max(jnp.abs(st.p))) > 0
    assert float(jnp.sum(traces ** 2)) > 0


def test_sponge_absorbs_energy():
    """With the source off after t0, total field energy must decay under
    the sponge (no reflecting boundary blowup)."""
    cfg = FWIConfig(nz=96, nx=96, timesteps=300, n_shots=1,
                    sponge_width=24, sponge_strength=0.02)
    st_mid, _ = run_forward(cfg, steps=150)
    e_mid = float(jnp.sum(st_mid.p ** 2))
    st_end, _ = run_forward(cfg, state=st_mid, steps=150)
    e_end = float(jnp.sum(st_end.p ** 2))
    assert e_end < e_mid


def test_velocity_model_has_salt_dome():
    v = np.asarray(velocity_model(CFG))
    assert v.min() >= 1500.0 and v.max() == 4500.0
    assert (v == 4500.0).sum() > 100  # dome exists


def test_cfl_stability():
    """(v·dt/dx) must satisfy the 4th-order 2-D CFL bound."""
    v = float(np.max(np.asarray(velocity_model(CFG))))
    courant = v * CFG.dt / CFG.dx
    assert courant < 0.606, f"CFL violated: {courant}"


def test_sharded_single_stripe_equals_reference():
    st_ref, _ = run_forward(CFG, steps=40)
    mesh = stripe_mesh(1)
    run, place, k = make_sharded_scan_runner(CFG, mesh, k=1)
    s = ShotState.init(CFG)
    p, pp = place((s.p, s.p_prev))
    for t in range(40):                      # one step per dispatch
        p, pp, _ = run(p, pp, t, 1)
    np.testing.assert_allclose(np.asarray(p), np.asarray(st_ref.p),
                               atol=1e-10)


def test_pallas_path_equals_reference():
    st_ref, _ = run_forward(CFG, steps=40)
    st_pal, _ = run_forward(CFG, use_pallas=True, steps=40)
    np.testing.assert_allclose(np.asarray(st_pal.p), np.asarray(st_ref.p),
                               atol=1e-9)


def test_checkpoint_restart_mid_run():
    """Fig.1 steps 2+7: stop, snapshot, restart — bit-identical result."""
    st_full, _ = run_forward(CFG, steps=50)
    st_a, _ = run_forward(CFG, steps=25)
    snap = {"p": np.asarray(st_a.p), "p_prev": np.asarray(st_a.p_prev),
            "t": st_a.t}
    st_b = ShotState(p=jnp.asarray(snap["p"]),
                     p_prev=jnp.asarray(snap["p_prev"]), t=snap["t"])
    st_b, _ = run_forward(CFG, state=st_b, steps=25)
    np.testing.assert_array_equal(np.asarray(st_full.p), np.asarray(st_b.p))


def test_halo_bytes_small():
    """Paper §3.3: striped partitioning keeps messages tiny (21 KB there;
    here 2 cols × NZ × shots × 4 B per seam per step)."""
    b = halo_bytes_per_step(CFG, 4)
    assert b == 2 * 2 * CFG.nz * CFG.n_shots * 4
    assert b < 64 * 1024


_MULTI_STRIPE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp, numpy as np
from repro.fwi.solver import FWIConfig, ShotState, run_forward
from repro.fwi.domain import stripe_mesh, make_sharded_scan_runner

cfg = FWIConfig(nz=64, nx=128, timesteps=40, n_shots=2, sponge_width=8)
ref, _ = run_forward(cfg, steps=40)
mesh = stripe_mesh(4)
run, place, k = make_sharded_scan_runner(cfg, mesh, k=1)
s = ShotState.init(cfg)
p, pp = place((s.p, s.p_prev))
for t in range(40):                          # one step per dispatch
    p, pp, _ = run(p, pp, t, 1)
err = float(jnp.max(jnp.abs(np.asarray(p) - np.asarray(ref.p))))
assert err < 1e-10, f"halo exchange mismatch: {err}"
print("MULTI_STRIPE_OK", err)
"""


def test_multi_stripe_halo_exchange_subprocess():
    """4-way striped decomposition with ppermute halo exchange matches
    the single-device solver exactly (run with 4 host devices)."""
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _MULTI_STRIPE_SCRIPT, src],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MULTI_STRIPE_OK" in out.stdout
