"""Streamed VMEM-tiled stencil pipeline tests (DESIGN.md §15).

Capacity ladder for production-scale grids:

  strips-ref (XLA)      == wave_block_ref            (BITWISE — strip
                           tiling is a pure re-slicing of the same ops)
  streamed Pallas       == wave_block_ref            (allclose ≤ 1e-5,
                           same contract as the resident Pallas kernel)
  streamed Pallas       == resident Pallas           (BITWISE — both run
                           the one _trapezoid_k_steps body)
  pipeline schedule     == overlap == fused          (BITWISE across
                           REAL stripe seams, 2 and 4 stripes)

plus the capacity bookkeeping (should_stream / stream_vmem_bytes /
pick_bz_stream refuses the whole-height fallback), the tall-grid
StripFallbackWarning on the resident pickers, and the planner's seam
provenance: sim scenarios consume the measured-probe overlapped seam,
not the dispatch-latency floor.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.stencil.kernel import (
    DEFAULT_VMEM_BUDGET,
    HALO,
    StripFallbackWarning,
    pick_bz,
    pick_bz_block,
    pick_bz_stream,
    resident_vmem_bytes,
    should_stream,
    stream_vmem_bytes,
    wave_block_pallas,
    wave_block_stream_pallas,
)
from repro.kernels.stencil.ops import wave_block
from repro.kernels.stencil.ref import wave_block_ref, wave_block_strips_ref

SMALL_BUDGET = 4 * 1024 * 1024          # forces multi-strip streaming


def _fields(nz, nx, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    p = jax.random.normal(ks[0], (nz, nx), jnp.float32)
    pp = jax.random.normal(ks[1], (nz, nx), jnp.float32)
    v = jax.random.uniform(ks[2], (nz, nx), jnp.float32, 0.05, 0.2)
    s = jnp.clip(jax.random.uniform(ks[3], (nz, nx)), 0.9, 1.0)
    return p, pp, v, s


# ------------------------------------------------- production-scale grid


@pytest.mark.parametrize("k", [1, 4, 8])
def test_streamed_2048_grid_matches_ref(k):
    """2048×2048 under a forced 4 MiB budget (a grid the whole-array
    resident design cannot hold): the XLA strips mirror is BITWISE equal
    to ``wave_block_ref`` and the streamed Pallas kernel (interpret mode
    off-TPU — real BlockSpec/DMA semantics) matches to the documented
    1e-5, with NO whole-height fallback (win < nz)."""
    nz = nx = 2048
    assert should_stream(nz, nx, k, vmem_budget=SMALL_BUDGET)
    bz = pick_bz_stream(nz, nx, k, vmem_budget=SMALL_BUDGET)
    assert bz + 2 * k * HALO < nz          # genuinely multi-strip
    p, pp, v, s = _fields(nz, nx, seed=k)
    srcv = jnp.linspace(0.5, 1.0, k)
    zi, xi = nz // 3, nx // 2
    ref = wave_block_ref(p, pp, v, s, srcv, zi, xi, receiver_row=7)

    strips = wave_block_strips_ref(p, pp, v, s, srcv, zi, xi,
                                   receiver_row=7, bz=bz)
    for a, b in zip(ref, strips):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    streamed = wave_block_stream_pallas(
        p, pp, v, s, srcv, zi, xi, receiver_row=7, bz=bz,
        vmem_budget=SMALL_BUDGET,
    )
    for a, b in zip(ref, streamed):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5)


@pytest.mark.parametrize("k", [1, 4])
def test_streamed_pallas_bitwise_vs_resident_pallas(k):
    """Streamed and resident Pallas kernels share one trapezoid body
    (_trapezoid_k_steps): identical strip geometry must produce BITWISE
    identical fields and traces — the DMA pipeline is pure data
    movement."""
    nz, nx = 128, 160
    bz = 16
    p, pp, v, s = _fields(nz, nx, seed=20 + k)
    srcv = jnp.linspace(0.5, 1.0, k)
    a = wave_block_pallas(p, pp, v, s, srcv, 40, 80, receiver_row=3,
                          bz=bz)
    b = wave_block_stream_pallas(p, pp, v, s, srcv, 40, 80,
                                 receiver_row=3, bz=bz)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("bz", [25, 50])
def test_strips_ref_unaligned_and_degenerate_windows(bz):
    """Non-8-aligned strips and the win==nz degenerate case stay
    bitwise (the strips mirror must cover every geometry
    ``pick_bz_stream``'s unaligned fallback can emit)."""
    nz, nx = 250, 96
    k = 4
    p, pp, v, s = _fields(nz, nx, seed=5)
    srcv = jnp.linspace(0.2, 0.9, k)
    ref = wave_block_ref(p, pp, v, s, srcv, 100, 30, receiver_row=2)
    out = wave_block_strips_ref(p, pp, v, s, srcv, 100, 30,
                                receiver_row=2, bz=bz)
    for a, b in zip(ref, out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------- auto-dispatch


def test_wave_block_auto_streams_over_budget():
    """ops.wave_block with stream=None must auto-select the streamed
    tiling when the resident footprint exceeds the budget — and stay
    BITWISE on the XLA path while doing so."""
    nz, nx, k = 512, 512, 4
    budget = 1 * 1024 * 1024
    assert should_stream(nz, nx, k, vmem_budget=budget)
    assert not should_stream(nz, nx, k)    # default budget holds 512²
    p, pp, v, s = _fields(nz, nx, seed=9)
    srcv = jnp.linspace(0.5, 1.0, k)
    ref = wave_block_ref(p, pp, v, s, srcv, 17, 400, receiver_row=1)
    out = wave_block(p, pp, v, s, srcv, 17, 400, receiver_row=1,
                     vmem_budget=budget)
    for a, b in zip(ref, out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_autotune_dispatches_to_stream_space():
    """autotune_bz_k(stream=True) searches the streamed (strip, depth)
    space: the winner must satisfy the divisor + trapezoid + budget
    constraints (and never the whole-height fallback)."""
    from repro.kernels.stencil.kernel import autotune_bz_k

    nz, nx = 128, 128
    budget = 512 * 1024
    bz, k = autotune_bz_k(nz, nx, bz_candidates=(8, 16, 32),
                          k_candidates=(1, 2), repeats=1, stream=True,
                          vmem_budget=budget)
    assert nz % bz == 0 and k in (1, 2)
    assert bz + 2 * k * HALO <= nz
    assert stream_vmem_bytes(nz, nx, bz, k) <= budget


# ------------------------------------------------- capacity bookkeeping


def test_vmem_accounting_motivates_streaming():
    """The numbers behind DESIGN.md §15's capacity table: 4096² cannot
    be VMEM-resident (256 MB ≫ 16 MB) but streams in O(bz·nx); the
    streamed footprint is NZ-independent."""
    nz = nx = 4096
    k = 4
    assert resident_vmem_bytes(nz, nx, k) > 16 * DEFAULT_VMEM_BUDGET
    assert should_stream(nz, nx, k)
    assert not should_stream(600, 600, k)  # paper grid stays resident
    bz = pick_bz_stream(nz, nx, k)
    assert stream_vmem_bytes(nz, nx, bz, k) <= DEFAULT_VMEM_BUDGET
    # streamed footprint depends on the strip, not the field height
    assert stream_vmem_bytes(nz, nx, 32, k) == \
        stream_vmem_bytes(8 * nz, nx, 32, k)


def test_pick_bz_stream_refuses_whole_height():
    """No silent whole-field fallback on the streamed path: geometries
    that cannot stream under the budget raise instead of quietly going
    resident (the exact footgun the resident pickers only warn about)."""
    with pytest.raises(ValueError):
        pick_bz_stream(251, 128, 4)              # prime nz: no divisor
    with pytest.raises(ValueError):
        pick_bz_stream(2048, 2048, 4, vmem_budget=64 * 1024)
    with pytest.raises(ValueError):
        pick_bz_stream(16, 128, 8)               # too short for k=8


def test_resident_pickers_warn_on_whole_height_fallback():
    """Tall grids with no usable strip divisor fall back to ONE
    whole-height resident strip — now loudly."""
    with pytest.warns(StripFallbackWarning):
        assert pick_bz(251) == 251
    with pytest.warns(StripFallbackWarning):
        assert pick_bz_block(1009, 4) == 1009
    # small / composite grids take the normal branch silently
    import warnings as _w

    with _w.catch_warnings():
        _w.simplefilter("error", StripFallbackWarning)
        assert pick_bz(600) == 120
        assert pick_bz(64) == 64
        assert pick_bz(37) == 37               # short prime: under cap
        assert pick_bz_block(600, 4) == 120


# -------------------------------------------- sharded pipeline schedule


_PIPELINE_INVARIANCE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp, numpy as np
from repro.fwi.solver import FWIConfig, ShotState, run_forward
from repro.fwi.domain import stripe_mesh, make_sharded_scan_runner

cfg = FWIConfig(nz=64, nx=128, timesteps=40, n_shots=2, sponge_width=8)
ref, ref_tr = run_forward(cfg, steps=40)
for n in (2, 4):
    outs = {}
    for sched in ("fused", "overlap", "pipeline"):
        run, place, keff = make_sharded_scan_runner(
            cfg, stripe_mesh(n), k=4, overlap=sched
        )
        s = ShotState.init(cfg)
        p, pp = place((s.p, s.p_prev))
        p, pp, tr = run(p, pp, 0, 40 // keff)
        outs[sched] = (np.asarray(p), np.asarray(pp), np.asarray(tr))
    # the double-buffered pipeline must be BITWISE identical to the
    # eager-exchange schedule (same per-block op graph, reordered);
    # vs the comm-avoiding fused window the op sequence is identical
    # but fusion shapes may flush denormal wavefront tails differently
    # (same contract as test_fused_engine) — equal up to sub-normal
    # noise (< FLT_MIN = 1.2e-38)
    for a, b in zip(outs["pipeline"], outs["overlap"]):
        assert np.array_equal(a, b), n
    for a, b in zip(outs["pipeline"], outs["fused"]):
        err = np.max(np.abs(a - b))
        assert err < 1.2e-38, (n, err)
    assert np.max(np.abs(outs["pipeline"][0] - np.asarray(ref.p))) < 1e-6, n
    assert np.max(np.abs(outs["pipeline"][2] - np.asarray(ref_tr))) < 1e-6, n
print("PIPELINE_INVARIANCE_OK")
"""


def test_pipeline_schedule_invariance_subprocess():
    """Double-buffered halo pipeline vs eager exchange vs fused window
    across 2- and 4-stripe REAL seams (4 host devices): bitwise
    invariant, and allclose to the seed reference."""
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _PIPELINE_INVARIANCE, src],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "PIPELINE_INVARIANCE_OK" in out.stdout


def test_pick_schedule_and_normalization():
    from repro.fwi.domain import _as_schedule, pick_schedule

    assert pick_schedule(4, "tpu") == "pipeline"
    assert pick_schedule(4, "cpu") == "fused"
    # one stripe has no seam: the single window on any backend
    assert pick_schedule(1, "tpu") == pick_schedule(1, "cpu") == "single"
    for n in (1, 2):
        assert _as_schedule(None, n) == pick_schedule(n)
        assert _as_schedule(True, n) == "overlap"     # legacy bool knob
        assert _as_schedule(False, n) == "fused"
        assert _as_schedule("pipeline", n) == "pipeline"
        with pytest.raises(ValueError):
            _as_schedule("bogus", n)


# ------------------------------------------------- seam provenance


def test_planner_consumes_probe_fed_overlapped_seam():
    """The fleet scenarios' OverheadModel must be built from the
    MEASURED seam probe through ``with_overlapped_seam`` — charging only
    the un-hidden residue — and not the ``with_measured_seam`` dispatch
    floor (which ignores the pipeline's overlap entirely)."""
    from repro.core import OverheadModel
    from repro.sim.scenarios import (
        OVERHEADS,
        SEAM_PROBE,
        overheads_from_probe,
    )

    om_probe = OverheadModel().with_overlapped_seam(
        SEAM_PROBE["plan"], SEAM_PROBE["ppermute_latency_s"],
        SEAM_PROBE["interior_compute_s_per_step"],
    )
    assert OVERHEADS.seam_s_per_step() == om_probe.seam_s_per_step()
    assert OVERHEADS.seam_latency_s == om_probe.seam_latency_s

    om_floor = OverheadModel().with_measured_seam(
        SEAM_PROBE["plan"], SEAM_PROBE["ppermute_latency_s"]
    )
    # the floor is real and nonzero; the probe shows the pipeline hides
    # it completely behind the measured stripe-interior compute
    assert om_floor.seam_s_per_step() > 0.0
    assert om_probe.seam_s_per_step() == 0.0
    assert OVERHEADS.seam_s_per_step() != om_floor.seam_s_per_step()

    # rebuilding from the committed snapshot is the one sanctioned path
    om2 = overheads_from_probe(SEAM_PROBE)
    assert om2.seam_latency_s == OVERHEADS.seam_latency_s
