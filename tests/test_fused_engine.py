"""Overlap-and-fuse propagation engine tests.

Equivalence ladder for the fused engine:
  pad-slice laplacian  == roll laplacian          (bitwise)
  block runner         == per-step reference loop (bitwise, incl. traces)
  wave_block (XLA)     == k sequential ref steps  (BITWISE — the fused
                          block is a pure re-scheduling of the same ops)
  wave_block (Pallas)  == same, to documented allclose tolerance (the
                          kernel's z/x stencil accumulation order
                          differs from the reference)
  sharded k-step scan runner == reference         (bitwise on the XLA
                          path, incl. across REAL stripe seams)
plus the communication claims: ppermute count per timestep drops k×,
the halo-plan bookkeeping (incl. overlap fields) matches the lowered
HLO, and the launch-boundary HBM proxy drops k× for fused blocks.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fwi.domain import (
    crop,
    effective_block,
    halo_bytes_per_step,
    halo_exchange_plan,
    make_sharded_scan_runner,
    stripe_mesh,
)
from repro.fwi.solver import (
    FWIConfig,
    ShotState,
    make_block_runner,
    ricker,
    run_forward,
    sponge_taper,
    velocity_model,
)
from repro.kernels.stencil.ops import wave_block
from repro.kernels.stencil.ref import laplacian, laplacian_roll, wave_step_ref

CFG = FWIConfig(nz=64, nx=128, timesteps=48, n_shots=2, sponge_width=8)


# ------------------------------------------------------------ solver layer


def test_laplacian_pad_equals_roll_bitwise():
    p = jax.random.normal(jax.random.key(3), (2, 96, 80), jnp.float32)
    a = laplacian_roll(p)
    b = laplacian(p)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _per_step_loop(cfg, steps):
    """The engine's oracle: one jitted ``wave_step_ref`` timestep at a
    time for the whole batch, each shot's source injected after it and
    the receiver row read out.  Returns (p, p_prev, traces (S, T, NX))."""
    v2dt2 = (velocity_model(cfg) * cfg.dt / cfg.dx) ** 2
    sponge = sponge_taper(cfg)
    wavelet = ricker(cfg)
    pos = cfg.shot_positions()
    shots = np.arange(cfg.n_shots)

    @jax.jit
    def step(p, pp, t):
        pn, pd = wave_step_ref(p, pp, v2dt2, sponge)
        src = wavelet[jnp.clip(t, 0, cfg.timesteps - 1)] * (cfg.dt ** 2)
        pn = pn.at[shots, pos[:, 0], pos[:, 1]].add(src)
        return pn, pd, pn[:, cfg.receiver_depth, :]

    st = ShotState.init(cfg)
    p, pp = st.p, st.p_prev
    traces = []
    for t in range(steps):
        p, pp, tr = step(p, pp, t)
        traces.append(tr)
    return p, pp, jnp.stack(traces, axis=1)


def test_scan_runner_traces_equal_per_step_loop():
    """The fused block runner must reproduce the per-step reference loop
    exactly, receiver traces included."""
    p, _, loop_tr = _per_step_loop(CFG, CFG.timesteps)
    st_scan, scan_tr = run_forward(CFG)
    np.testing.assert_array_equal(np.asarray(st_scan.p), np.asarray(p))
    np.testing.assert_array_equal(np.asarray(scan_tr), np.asarray(loop_tr))


def test_scan_runner_restart_offset_no_retrace():
    """t0 is traced: restarting mid-run reuses the compiled runner and
    matches the straight-through run and the per-step reference loop
    bit-for-bit."""
    run = make_block_runner(CFG, collect_traces=True)
    st = ShotState.init(CFG)
    p_a, pp_a, tr_a = run(st.p, st.p_prev, 0, 48)
    p_b, pp_b, tr1 = run(st.p, st.p_prev, 0, 24)
    p_b, pp_b, tr2 = run(p_b, pp_b, 24, 24)
    np.testing.assert_array_equal(np.asarray(p_a), np.asarray(p_b))
    np.testing.assert_array_equal(
        np.asarray(tr_a), np.asarray(jnp.concatenate([tr1, tr2], axis=1))
    )
    p_l, pp_l, tr_l = _per_step_loop(CFG, 48)
    for a, b in ((p_a, p_l), (pp_a, pp_l), (tr_a, tr_l)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_model_building_memoized():
    assert velocity_model(CFG) is velocity_model(CFG)
    run = make_block_runner(CFG, collect_traces=False)
    assert make_block_runner(CFG, collect_traces=False) is run
    assert make_block_runner(CFG) is make_block_runner(CFG)
    # the memoized runner is the engine the per-step loop pins
    st = ShotState.init(CFG)
    p, pp = run(st.p, st.p_prev, 0, 8)
    p_l, pp_l, _ = _per_step_loop(CFG, 8)
    np.testing.assert_array_equal(np.asarray(p), np.asarray(p_l))
    np.testing.assert_array_equal(np.asarray(pp), np.asarray(pp_l))


# ------------------------------------------------- temporal blocking layer


@pytest.mark.parametrize("k", [2, 4, 8])
def test_temporal_block_equals_sequential_ref(k):
    """One k-step block (single halo exchange) == k sequential reference
    steps, to well under the 1e-4 acceptance tolerance."""
    ref, ref_tr = run_forward(CFG, steps=CFG.timesteps)
    mesh = stripe_mesh(1)
    run, place, keff = make_sharded_scan_runner(CFG, mesh, k=k)
    assert keff == k
    s = ShotState.init(CFG)
    p, pp = place((s.p, s.p_prev))
    trs = []
    for b in range(CFG.timesteps // k):      # one block per dispatch
        p, pp, tr = run(p, pp, b * k, 1)
        trs.append(tr)
    tr = jnp.concatenate(trs, axis=1)
    np.testing.assert_allclose(np.asarray(p), np.asarray(ref.p), atol=1e-6)
    np.testing.assert_allclose(np.asarray(tr), np.asarray(ref_tr),
                               atol=1e-6)


def test_sharded_scan_runner_equals_reference():
    ref, ref_tr = run_forward(CFG, steps=CFG.timesteps)
    run, place, k = make_sharded_scan_runner(CFG, stripe_mesh(1), k=4)
    s = ShotState.init(CFG)
    p, pp = place((s.p, s.p_prev))
    p, pp, tr = run(p, pp, 0, CFG.timesteps // k)
    np.testing.assert_allclose(np.asarray(p), np.asarray(ref.p), atol=1e-6)
    np.testing.assert_allclose(np.asarray(tr), np.asarray(ref_tr),
                               atol=1e-6)


_MULTI_STRIPE_BLOCKED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp, numpy as np
from repro.fwi.solver import FWIConfig, ShotState, run_forward
from repro.fwi.domain import stripe_mesh, make_sharded_scan_runner

cfg = FWIConfig(nz=64, nx=128, timesteps=40, n_shots=2, sponge_width=8)
ref, ref_tr = run_forward(cfg, steps=40)
for k in (2, 4, 8):
    for n in (2, 4):
        run, place, keff = make_sharded_scan_runner(cfg, stripe_mesh(n), k=k)
        assert place.schedule == "pipeline", place.schedule
        s = ShotState.init(cfg)
        p, pp = place((s.p, s.p_prev))
        trs = []
        for b in range(40 // keff):          # one block per dispatch
            p, pp, tr = run(p, pp, b * keff, 1)
            trs.append(tr)
        tr = jnp.concatenate(trs, axis=1)
        # the pipelined XLA block path is pinned BITWISE equal to the
        # seed reference, seams included
        assert np.array_equal(np.asarray(p), np.asarray(ref.p)), (k, n)
        assert np.array_equal(np.asarray(tr), np.asarray(ref_tr)), (k, n)

# shot-parallel fused runner: zero-communication first-level split;
# contract is f32-ULP allclose (per-device batch changes XLA's
# vectorization/FMA contraction), documented in the factory docstring
from repro.fwi.solver import make_shot_parallel_runner
run_sp, place_sp = make_shot_parallel_runner(cfg, 2, k=4)
s = ShotState.init(cfg)
p, pp = place_sp((s.p, s.p_prev))
p, pp, tr = run_sp(p, pp, 0, 40)
scale = float(np.max(np.abs(np.asarray(ref.p)))) or 1.0
perr = np.max(np.abs(np.asarray(p) - np.asarray(ref.p))) / scale
terr = np.max(np.abs(np.asarray(tr) - np.asarray(ref_tr))) / scale
assert perr < 1e-6 and terr < 1e-6, (perr, terr)
print("BLOCKED_MULTI_STRIPE_OK")
"""


def test_temporal_block_multi_stripe_subprocess():
    """Temporal blocking across REAL stripe boundaries (4 host devices):
    k-step blocks with one packed exchange match the reference for
    several (k, stripe-count) combinations."""
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _MULTI_STRIPE_BLOCKED, src],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "BLOCKED_MULTI_STRIPE_OK" in out.stdout


_PPERMUTES = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, sys.argv[1])
import jax.numpy as jnp
from repro.fwi.domain import make_sharded_scan_runner, stripe_mesh
from repro.fwi.solver import FWIConfig

cfg = FWIConfig(nz=64, nx=128, timesteps=48, n_shots=2, sponge_width=8)
z = jnp.zeros((cfg.n_shots, cfg.nz, cfg.nx), jnp.float32)
for k in (1, 4):
    run, place, keff = make_sharded_scan_runner(cfg, stripe_mesh(2), k=k)
    for blocks in (1, 3):
        txt = run.lower(*place((z, z)), 0, blocks=blocks).as_text()
        n = txt.count("collective_permute") + txt.count("collective-permute")
        print("k", keff, "blocks", blocks, "ppermutes", n)
"""


def test_ppermute_count_drops_k_fold():
    """k=4 temporal blocking must emit the SAME 2 collective-permutes
    per block as k=1 — i.e. 4× fewer per timestep.  The two-stripe
    pipeline's program holds its prologue's 2 and the scan body's 2,
    whatever the number of blocks (one stripe exchanges nothing)."""
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _PPERMUTES, src],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.split("\n")
    for k in (1, 4):                       # per-timestep: 2 vs 0.5 = 4x
        for blocks in (1, 3):
            assert f"k {k} blocks {blocks} ppermutes 4" in lines, out.stdout


def test_halo_exchange_plan_bookkeeping():
    # seed formula preserved at k=1
    assert halo_bytes_per_step(CFG, 4) == 2 * 2 * CFG.nz * CFG.n_shots * 4
    plan1 = halo_exchange_plan(CFG, 4, k=1)
    plan4 = halo_exchange_plan(CFG, 4, k=4)
    assert plan1["ppermutes_per_step"] == 2.0
    assert plan4["ppermutes_per_step"] == 0.5
    assert plan4["steps_per_exchange"] == 4
    # packed p+p_prev edges: amortized bytes exactly 2x the k=1 stream
    assert plan4["bytes_per_step"] == 2 * plan1["bytes_per_step"]
    # k clamps so the overlap fits in a stripe, and the clamped value is
    # exposed on the block step so callers advance t0 correctly
    assert effective_block(CFG, CFG.nx // 2, 64) == 1
    _, _, k = make_sharded_scan_runner(CFG, stripe_mesh(1), k=4)
    assert k == 4


def test_effective_block_keeps_overlap_inside_stripe():
    """Regression: the clamp must keep the boundary-window source
    regions (2·k·HALO columns each side) inside one stripe for ANY
    requested k — otherwise the interior/boundary split would read
    columns a stripe does not own."""
    from repro.fwi.domain import HALO

    for n in (1, 2, 4, 8, 16, 32):
        if CFG.nx % n:
            continue
        nxl = CFG.nx // n
        for k in (1, 2, 4, 8, 64, 1000):
            keff = effective_block(CFG, n, k)
            assert 1 <= keff <= k
            assert 2 * keff * HALO <= nxl or keff == 1, (n, k, keff)


def test_halo_exchange_plan_overlap_fields():
    plan = halo_exchange_plan(CFG, 4, k=4)
    nxl = CFG.nx // 4
    pad = plan["k"] * 2
    assert plan["interior_cols"] == nxl
    assert plan["boundary_cols"] == 6 * pad
    assert 0.0 < plan["overlap_fraction"] < 1.0
    assert plan["overlap_fraction"] == nxl / (nxl + 6 * pad)
    # more stripes -> narrower stripes -> less hidable work
    wide = halo_exchange_plan(CFG, 1, k=4)["overlap_fraction"]
    narrow = halo_exchange_plan(CFG, 8, k=4)["overlap_fraction"]
    assert narrow < wide


def test_overhead_model_overlapped_seam():
    from repro.core import OverheadModel

    plan = halo_exchange_plan(CFG, 4, k=4)
    om_measured = OverheadModel().with_measured_seam(plan, 1e-3)
    # unknown compute time -> no overlap credit: degrades to measured
    om0 = OverheadModel().with_overlapped_seam(plan, 1e-3, 0.0)
    assert om0.seam_s_per_step() == om_measured.seam_s_per_step()
    # interior compute larger than the seam -> fully hidden
    om_hidden = OverheadModel().with_overlapped_seam(plan, 1e-3, 1.0)
    assert om_hidden.seam_s_per_step() == 0.0
    # partial hiding: residue = seam_block - interior_block, monotone
    seam_block = plan["ppermutes_per_exchange"] * 1e-3
    t_c = 0.5 * seam_block / (
        plan["steps_per_exchange"] * plan["overlap_fraction"]
    )
    om_half = OverheadModel().with_overlapped_seam(plan, 1e-3, t_c)
    assert 0.0 < om_half.seam_latency_s < seam_block
    np.testing.assert_allclose(om_half.seam_latency_s, seam_block / 2)


# --------------------------------------------------- fused block kernel


def _sequential_ref(p, pp, v, s, srcv, zi, xi, rrow):
    """k seed-form steps + injection + receiver rows — the oracle the
    fused block must reproduce."""
    from repro.kernels.stencil.ref import wave_step_ref

    traces = []
    for j in range(srcv.shape[0]):
        pn, pd = wave_step_ref(p, pp, v, s)
        pn = pn.at[zi, xi].add(srcv[j])
        traces.append(pn[rrow])
        p, pp = pn, pd
    return p, pp, jnp.stack(traces)


def _one_shot_block(p, pp, v, s, srcv, zi, xi, **kw):
    """``wave_block`` on one shot: the (NZ, NX) fields as the batch S=1,
    its outputs back as (NZ, NX) fields and (k, NX) traces."""
    out = wave_block(p[None], pp[None], v, s, srcv, jnp.array([zi]),
                     jnp.array([xi]), **kw)
    return tuple(o[0] for o in out)


def _block_fields(nz, nx, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    p = jax.random.normal(ks[0], (nz, nx), jnp.float32)
    pp = jax.random.normal(ks[1], (nz, nx), jnp.float32)
    v = jax.random.uniform(ks[2], (nz, nx), jnp.float32, 0.05, 0.2)
    s = jnp.clip(jax.random.uniform(ks[3], (nz, nx)), 0.9, 1.0)
    return p, pp, v, s


@pytest.mark.parametrize("k", [1, 2, 4, 8])
def test_wave_block_xla_bitwise_vs_sequential_ref(k):
    """The pure-XLA fused block is a re-scheduling of the identical ops:
    BITWISE equal to k sequential seed-form steps (random fields put
    energy at every physical domain edge)."""
    nz, nx = 64, 96
    p, pp, v, s = _block_fields(nz, nx, seed=k)
    srcv = jnp.linspace(0.5, 1.0, k)
    zi, xi = nz // 3, nx // 2
    a = _sequential_ref(p, pp, v, s, srcv, zi, xi, 2)
    b = _one_shot_block(p, pp, v, s, srcv, zi, xi, receiver_row=2)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("k", [1, 2, 4, 8])
@pytest.mark.parametrize("bz", [8, 32, None])
def test_wave_block_pallas_matches_ref(k, bz):
    """Pallas trapezoid kernel vs the sequential reference across
    (bz, k).  Contract: allclose at 5e-5 (NOT bitwise — the kernel
    accumulates the z then x stencil rings, the reference interleaves
    them per ring; each inner step compounds ~1e-6)."""
    nz, nx = 64, 96
    p, pp, v, s = _block_fields(nz, nx, seed=10 + k)
    srcv = jnp.linspace(0.5, 1.0, k)
    zi, xi = 1, nx - 2            # source ON the corner boundary region
    a = _sequential_ref(p, pp, v, s, srcv, zi, xi, 2)
    b = _one_shot_block(p, pp, v, s, srcv, zi, xi, receiver_row=2,
                        use_pallas=True, bz=bz)
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=5e-5)


def test_wave_block_single_strip_fallback():
    """Grids too short for any multi-strip trapezoid (prime nz) run as
    one whole-height strip and still match."""
    from repro.kernels.stencil.kernel import pick_bz_block

    nz, nx = 37, 64
    assert pick_bz_block(nz, 8) == nz
    p, pp, v, s = _block_fields(nz, nx, seed=3)
    srcv = jnp.ones((8,)) * 0.5
    a = _sequential_ref(p, pp, v, s, srcv, 5, 6, 1)
    b = _one_shot_block(p, pp, v, s, srcv, 5, 6, receiver_row=1,
                        use_pallas=True)
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=5e-5)


def test_block_runner_factories_key_on_full_knobs():
    """Memoized factories must key on (k, bz, use_pallas) so variants
    don't collide in the cache — and still hit for equal args."""
    a = make_block_runner(CFG, k=2)
    assert make_block_runner(CFG, k=2) is a
    assert make_block_runner(CFG, k=4) is not a
    assert make_block_runner(CFG, k=2, bz=8) is not a
    assert make_block_runner(CFG, k=2, use_pallas=True) is not a
    m1, _, _ = make_sharded_scan_runner(CFG, stripe_mesh(1), k=2)
    m2, _, _ = make_sharded_scan_runner(CFG, stripe_mesh(1), k=2, bz=8)
    m3, _, _ = make_sharded_scan_runner(CFG, stripe_mesh(1), k=2)
    assert m1 is m3 and m1 is not m2


def test_entry_boundary_bytes_drops_k_fold():
    """The launch-boundary HBM proxy: a k-step fused block moves the
    wavefields across the jit boundary once per k steps."""
    from repro.launch.hlo_cost import entry_boundary_bytes

    nz, nx, k = 64, 96, 4
    p, pp, v, s = _block_fields(nz, nx)
    p, pp = p[None], pp[None]

    def block(steps):
        return jax.jit(
            lambda a, b, vv, ss, sv: wave_block(a, b, vv, ss, sv, 3, 4)
        ).lower(p, pp, v, s, jnp.zeros((steps,))).compile()

    f_step, f_blk = block(1), block(k)
    shape = (nz, nx)
    sb = entry_boundary_bytes(f_step.as_text(), shape)
    bb = entry_boundary_bytes(f_blk.as_text(), shape)
    assert sb["n_params"] == 4 and sb["n_results"] == 2
    assert bb["n_params"] == 4 and bb["n_results"] == 2
    ratio = sb["total_bytes"] / (bb["total_bytes"] / k)
    assert ratio >= 2.0, ratio                   # acceptance: >= 2x at k=4
    np.testing.assert_allclose(ratio, k)


# --------------------------------------------------------- kernel layer


@pytest.mark.parametrize("bz", [8, 16, 32, 64, None])
def test_pallas_bz_sweep_matches_ref(bz):
    """The resident kernel, one shot and one step, vs the reference step
    across strip heights, including the auto-picked one (bz=None)."""
    nz, nx = 64, 256
    ks = jax.random.split(jax.random.key(7), 4)
    p = jax.random.normal(ks[0], (nz, nx), jnp.float32)
    pp = jax.random.normal(ks[1], (nz, nx), jnp.float32)
    v = jax.random.uniform(ks[2], (nz, nx), jnp.float32, 0.05, 0.2)
    sponge = jnp.clip(jax.random.uniform(ks[3], (nz, nx)), 0.9, 1.0)
    a1, a2 = wave_step_ref(p, pp, v, sponge)
    b1, b2, _ = _one_shot_block(p, pp, v, sponge, jnp.zeros((1,)), 0, 0,
                                use_pallas=True, bz=bz, stream=False)
    np.testing.assert_allclose(a1, b1, atol=3e-6)
    np.testing.assert_allclose(a2, b2, atol=3e-6)


def test_sharded_pallas_path_equals_reference():
    """use_pallas wired through the sharded local step: the fused kernel
    runs inside the shard_map region and matches the reference."""
    cfg = FWIConfig(nz=32, nx=64, timesteps=8, n_shots=1, sponge_width=4)
    ref, ref_tr = run_forward(cfg, steps=8)
    run, place, k = make_sharded_scan_runner(
        cfg, stripe_mesh(1), k=4, use_pallas=True
    )
    s = ShotState.init(cfg)
    p, pp = place((s.p, s.p_prev))
    trs = []
    for b in range(2):
        p, pp, tr = run(p, pp, b * k, 1)
        trs.append(tr)
    tr = jnp.concatenate(trs, axis=1)
    np.testing.assert_allclose(np.asarray(p), np.asarray(ref.p), atol=1e-5)
    np.testing.assert_allclose(np.asarray(tr), np.asarray(ref_tr),
                               atol=1e-5)


@pytest.mark.parametrize("k", [1, 2, 4])
def test_sharded_pallas_schedules_on_one_stripe(k):
    """The one-stripe schedule runs the shot-batched kernel at every
    block length — the whole stripe as one window — and matches the
    reference to the Pallas 1e-5."""
    cfg = FWIConfig(nz=64, nx=256, timesteps=16, n_shots=3, sponge_width=8)
    ref, ref_tr = run_forward(cfg, steps=16, use_pallas=False)
    run, place, keff = make_sharded_scan_runner(
        cfg, stripe_mesh(1), k=k, use_pallas=True
    )
    assert (place.schedule, keff) == ("single", k)
    s = ShotState.init(cfg)
    p, _, tr = run(*place((s.p, s.p_prev)), 0, 16 // k)
    scale = float(np.max(np.abs(np.asarray(ref.p))))
    np.testing.assert_allclose(np.asarray(p), np.asarray(ref.p),
                               atol=1e-5 * scale)
    np.testing.assert_allclose(np.asarray(tr), np.asarray(ref_tr),
                               atol=1e-5 * scale)


# --------------------------------------------- one stripe, one window

#: 61 × 203 streams under this VMEM budget, as 13601 × 2801 does under
#: the real one: shot tile 1, 8-row strips, padded to 64 × 256
SMALL_VMEM_BUDGET = 300_000
RAGGED = FWIConfig(nz=61, nx=203, timesteps=32, n_shots=2, sponge_width=4)


def _run_one_stripe(cfg, steps, **kw):
    """The one-stripe scan runner's carry (padded), logical fields and
    traces after ``steps`` steps from rest."""
    run, place, k = make_sharded_scan_runner(cfg, stripe_mesh(1), k=4, **kw)
    s = ShotState.init(cfg)
    p, pp, tr = run(*place((s.p, s.p_prev)), 0, steps // k)
    return place, (p, pp), (crop(cfg, p), crop(cfg, pp), tr)


@pytest.mark.parametrize("cfg", [CFG, RAGGED], ids=["aligned", "ragged"])
def test_one_stripe_default_is_the_single_window_bitwise(monkeypatch, cfg):
    """One stripe's schedule is the interior window alone, and on the
    XLA path it equals the reference bit for bit: fields and traces,
    with the padding of a ragged grid still exactly zero."""
    import repro.kernels.stencil.kernel as kernel

    ref, ref_tr = run_forward(cfg, steps=cfg.timesteps)
    monkeypatch.setattr(kernel, "DEFAULT_VMEM_BUDGET", SMALL_VMEM_BUDGET)
    place, carry, got = _run_one_stripe(cfg, cfg.timesteps)
    assert place.schedule == "single"
    assert place.geometry.padded(cfg) == (cfg is RAGGED)
    for a in carry:
        a = np.asarray(a)
        assert not a[..., cfg.nz:, :].any() and not a[..., cfg.nx:].any()
    for a, r in zip(got, (ref.p, ref.p_prev, ref_tr)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(r))


def test_one_stripe_default_pallas_matches_reference(monkeypatch):
    """The single window on the Pallas (interpret) path, streamed over a
    padded grid at shot tiles smaller than the batch, matches the
    reference to the Pallas 1e-5."""
    import repro.kernels.stencil.kernel as kernel

    ref, ref_tr = run_forward(RAGGED, steps=16, use_pallas=False)
    monkeypatch.setattr(kernel, "DEFAULT_VMEM_BUDGET", SMALL_VMEM_BUDGET)
    place, _, (p, pp, tr) = _run_one_stripe(RAGGED, 16, use_pallas=True)
    g = place.geometry
    assert place.schedule == "single"
    assert g.stream and g.shot_tile < RAGGED.n_shots
    scale = float(np.max(np.abs(np.asarray(ref.p))))
    for a, r in ((p, ref.p), (pp, ref.p_prev), (tr, ref_tr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=1e-5 * scale)


_SEAM_PHASES = ("collective-permute", "/fwi.exchange/", "/fwi.boundary/",
                "/fwi.stitch/")

_TWO_STRIPE_PHASES = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from repro.fwi.domain import (
    make_sharded_scan_runner, pick_schedule, stripe_mesh)
from repro.fwi.solver import FWIConfig

cfg = FWIConfig(nz=32, nx=64, timesteps=32, n_shots=2, sponge_width=4)
run, place, k = make_sharded_scan_runner(
    cfg, stripe_mesh(2), k=4, use_pallas=False)
z = jnp.zeros((cfg.n_shots, cfg.nz, cfg.nx), jnp.float32)
hlo = run.lower(*place((z, z)), 0, blocks=2).compile().as_text()
print("pick", pick_schedule(2))
for phase in sys.argv[2:] + ["/fwi.interior/"]:
    print(place.schedule, phase, phase in hlo)
"""


def test_single_window_drops_the_seam_phases_only_on_one_stripe():
    """The compiled one-stripe program holds the interior and none of
    the exchange, boundary windows or stitch; two stripes keep them all
    under "pipeline", on this host as on the TPU."""
    run, place, k = make_sharded_scan_runner(CFG, stripe_mesh(1), k=4)
    s = ShotState.init(CFG)
    hlo = run.lower(*place((s.p, s.p_prev)), 0, blocks=2).compile().as_text()
    assert "/fwi.interior/" in hlo
    for phase in _SEAM_PHASES + ("collective_permute",):
        assert phase not in hlo, phase

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    src = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run(
        [sys.executable, "-c", _TWO_STRIPE_PHASES, src, *_SEAM_PHASES],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.split("\n")
    assert "pick pipeline" in lines, out.stdout
    for phase in _SEAM_PHASES + ("/fwi.interior/",):
        assert f"pipeline {phase} True" in lines, out.stdout


def test_driver_checkpoint_carries_block_progress():
    """A mid-block checkpoint/restore must not re-dispatch the pending
    steps: physical timesteps stay in lockstep with logical steps."""
    from repro.core.orchestrator import PodSpec, Resources
    from repro.fwi.driver import FWISession, TimeModel

    cfg = FWIConfig(nz=32, nx=64, timesteps=32, n_shots=1, sponge_width=4)
    res = Resources(pods=[PodSpec(chips=1, name="cluster")], shares=[1.0])
    rng = np.random.default_rng(0)
    s = FWISession(cfg, res, 0, None, time_model=TimeModel(jitter=0.0),
                   rng=rng, exchange_interval=4, scan_block=8)
    for i in range(5):                      # mid-block: 3 steps pending
        s.run_step(i)
    snap = s.checkpoint(5)
    assert snap["t"] == 8 and snap["pending"] == 3
    s2 = FWISession(cfg, res, 5, snap, time_model=TimeModel(jitter=0.0),
                    rng=rng, exchange_interval=4, scan_block=8)
    for i in range(5, 16):
        s2.run_step(i)
    # 16 logical steps = exactly two blocks of 8 physical timesteps
    assert s2.t == 16


def test_interpret_auto_selects_off_tpu():
    from repro.kernels.stencil.kernel import (
        HALO, StripFallbackWarning, default_interpret, pick_bz_block,
    )

    if jax.default_backend() != "tpu":
        assert default_interpret() is True
    assert 600 % pick_bz_block(600, 1) == 0
    assert pick_bz_block(600, 1) % 8 == 0
    assert pick_bz_block(64, 1) == 32
    # strips shorter than the halo would silently mis-clamp the
    # neighbor-row slices: prime heights fall back to one whole strip
    with pytest.warns(StripFallbackWarning):
        assert pick_bz_block(251, 1) == 251
    assert pick_bz_block(127, 1) >= HALO


def test_step_and_block_share_interpret_default():
    """The resident and streamed kernels must agree on backend detection
    through the ONE shared helper — a drifted copy would silently run
    one kernel compiled and the other interpreted."""
    import inspect

    from repro.kernels.stencil import kernel, ops

    assert ops.default_interpret is kernel.default_interpret
    src_res = inspect.getsource(kernel.wave_block_shots_pallas)
    src_stm = inspect.getsource(kernel.wave_block_shots_stream_pallas)
    assert "default_interpret()" in src_res
    assert "default_interpret()" in src_stm


def test_pick_bz_block_and_pick_k():
    from repro.kernels.stencil.kernel import HALO, pick_bz_block, pick_k

    for nz in (32, 64, 128, 251, 600):
        for k in (1, 2, 4, 8):
            bz = pick_bz_block(nz, k)
            assert nz % bz == 0
            # either a real trapezoid fits, or whole-height fallback
            assert bz + 2 * k * HALO <= nz or bz == nz
        kk = pick_k(nz)
        assert 1 <= kk <= 8
    assert pick_k(600) == 8
    assert pick_bz_block(600, 8) == 120


def test_pallas_prime_height_auto_bz():
    """nz with no divisor in [HALO, cap] (prime 251) must still match
    the reference through the auto-picked single-strip path."""
    nz, nx = 251, 128
    ks = jax.random.split(jax.random.key(11), 4)
    p = jax.random.normal(ks[0], (nz, nx), jnp.float32)
    pp = jax.random.normal(ks[1], (nz, nx), jnp.float32)
    v = jax.random.uniform(ks[2], (nz, nx), jnp.float32, 0.05, 0.2)
    sponge = jnp.clip(jax.random.uniform(ks[3], (nz, nx)), 0.9, 1.0)
    a1, a2 = wave_step_ref(p, pp, v, sponge)
    b1, b2, _ = _one_shot_block(p, pp, v, sponge, jnp.zeros((1,)), 0, 0,
                                use_pallas=True)
    np.testing.assert_allclose(a1, b1, atol=3e-6)
    np.testing.assert_allclose(a2, b2, atol=3e-6)
