"""Streamed VMEM-tiled stencil pipeline tests (DESIGN.md §15).

Capacity ladder for production-scale grids:

  strips-ref (XLA)      == wave_block_ref            (BITWISE — strip
                           tiling is a pure re-slicing of the same ops)
  streamed Pallas       == wave_block_ref            (allclose ≤ 1e-5,
                           same contract as the resident Pallas kernel)
  streamed Pallas       == resident Pallas           (BITWISE — both run
                           the one _trapezoid_k_steps_shots body)
  pipeline schedule     == reference                 (BITWISE across
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
    pick_bz_block,
    pick_bz_stream,
    resident_vmem_bytes,
    should_stream,
    stream_vmem_bytes,
    wave_block_shots_pallas,
    wave_block_shots_stream_pallas,
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

    streamed = wave_block_shots_stream_pallas(
        p[None], pp[None], v, s, srcv, jnp.array([zi]), jnp.array([xi]),
        receiver_row=7, bz=bz, vmem_budget=SMALL_BUDGET,
    )
    for a, b in zip(ref, streamed):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b)[0],
                                   atol=1e-5)


@pytest.mark.parametrize("k", [1, 4])
def test_streamed_pallas_bitwise_vs_resident_pallas(k):
    """Streamed and resident Pallas kernels share one trapezoid body
    (_trapezoid_k_steps_shots): identical strip geometry must produce
    BITWISE identical fields and traces for one shot — the DMA pipeline
    is pure data movement."""
    nz, nx = 128, 160
    bz = 16
    p, pp, v, s = _fields(nz, nx, seed=20 + k)
    srcv = jnp.linspace(0.5, 1.0, k)
    at = (p[None], pp[None], v, s, srcv, jnp.array([40]), jnp.array([80]))
    a = wave_block_shots_pallas(*at, receiver_row=3, bz=bz)
    b = wave_block_shots_stream_pallas(*at, receiver_row=3, bz=bz)
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
    out = wave_block(p[None], pp[None], v, s, srcv, jnp.array([17]),
                     jnp.array([400]), receiver_row=1, vmem_budget=budget)
    for a, b in zip(ref, out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[0])


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
        assert pick_bz_block(251, 4) == 251
    with pytest.warns(StripFallbackWarning):
        assert pick_bz_block(1009, 4) == 1009
    # small / composite grids take the normal branch silently
    import warnings as _w

    with _w.catch_warnings():
        _w.simplefilter("error", StripFallbackWarning)
        assert pick_bz_block(600, 1) == 120
        assert pick_bz_block(64, 1) == 32
        assert pick_bz_block(16, 8) == 16      # too short for a trapezoid
        assert pick_bz_block(37, 8) == 37      # short prime: under cap
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
    run, place, keff = make_sharded_scan_runner(cfg, stripe_mesh(n), k=4)
    assert place.schedule == "pipeline", place.schedule
    s = ShotState.init(cfg)
    p, pp = place((s.p, s.p_prev))
    p, pp, tr = run(p, pp, 0, 40 // keff)
    # the double-buffered pipeline, all blocks in one dispatch, is
    # BITWISE identical to the reference: fields, damped previous
    # field and traces
    for a, b in ((p, ref.p), (pp, ref.p_prev), (tr, ref_tr)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), n
print("PIPELINE_INVARIANCE_OK")
"""


def test_pipeline_schedule_invariance_subprocess():
    """The double-buffered halo pipeline across 2- and 4-stripe REAL
    seams (4 host devices), many blocks in one dispatch: bitwise equal
    to the seed reference."""
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
    """The schedule follows the stripe count alone: one stripe has no
    seam, so the single window; the choice takes no backend."""
    import inspect

    from repro.fwi.domain import pick_schedule

    assert pick_schedule(1) == "single"
    assert list(inspect.signature(pick_schedule).parameters) == ["n_stripes"]


@pytest.mark.parametrize("n", [2, 4])
def test_pick_schedule_pipeline_on_every_backend(n):
    """Seams take the pipeline on this host too, the schedule the TPU
    runs and the tests pin bitwise to the reference."""
    from repro.fwi.domain import pick_schedule

    assert pick_schedule(n) == "pipeline"


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
