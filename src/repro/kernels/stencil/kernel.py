"""Pallas TPU kernel: fused 4th-order wave-equation timestep.

TPU adaptation of the paper's (CPU/MPI, Eigen-based) FWI hot loop —
re-blocked for the TPU memory hierarchy instead of ported:

* Row-strip tiling: each grid step owns a (BZ, NX) strip resident in
  VMEM.  The pressure field is passed ONCE with a whole-array BlockSpec
  whose index map is constant — the pipeline fetches it a single time
  and every grid step slices its strip plus the ±HALO neighbor rows out
  of the resident copy.  (The seed version passed `p` through THREE
  aliased BlockSpecs — center/up/down neighbor views — which costs 3×
  the HBM reads of the field per step; for a memory-bound stencil that
  was most of the budget.)  x-halo needs no exchange because strips span
  the full width, matching the paper's striped second-level partitioning
  that minimizes communication.
* One fused pass: Laplacian + leapfrog update + sponge damping for BOTH
  outputs (p_next, p_damped) — the fields are read once from HBM per
  step, which is the whole battle for a memory-bound stencil.
* f32 compute; (8,128)-aligned strips (BZ multiple of 8, NX multiple of
  128) keep loads/stores VPU-lane aligned.
* `interpret` auto-selects from the backend: compiled on TPU, interpret
  mode elsewhere (the kernel body runs with real Pallas semantics on
  CPU, validating the BlockSpec/halo logic).  `autotune_bz` sweeps strip
  heights and memoizes the fastest — the block-shape knob the ROADMAP's
  "fast as the hardware allows" goal turns.

Physical-boundary strips (first/last) zero their out-of-domain halo
rows, reproducing ref.py's zero-halo convention exactly.

Capacity: the constant-map whole-array spec keeps the full field in
VMEM (NZ·NX·4 B — 1.4 MB for the paper's 600² grid, comfortably under
the ~16 MB/core budget), which hard-caps the resident design at
~1k²-class grids.  Production surveys (≥ 4096² — DESIGN.md §15) run the
STREAMED kernel instead: ``wave_block_stream_pallas`` holds only a
double-buffered pair of (bz + 2·k·HALO, NX) haloed windows in VMEM and
DMAs strip i+1 in from HBM while strip i computes its k-step trapezoid
— ``stream_vmem_bytes`` is O(bz·NX), independent of NZ, so the grid
height is unbounded by VMEM.  ``pick_bz_stream`` sizes the strip under
an explicit budget and ``should_stream`` auto-selects the design per
(shape, budget); the XLA-path mirror of the same tiling is
``ref.py::wave_block_strips_ref`` (bit-exactness oracle).
"""
from __future__ import annotations

import functools
import time
import warnings

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

C0 = -5.0 / 2.0
C1 = 4.0 / 3.0
C2 = -1.0 / 12.0
HALO = 2

#: per-core VMEM working budget the tiling heuristics plan against
#: (TPU cores have ~16 MB; interpret mode has no hard cap but the
#: heuristics still honor it so CPU-validated tilings carry to TPU)
DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024


class StripFallbackWarning(UserWarning):
    """A grid with no usable strip divisor fell back to ONE whole-height
    strip — correct, but the whole field goes VMEM-resident (the tall-
    grid footgun the streamed path refuses outright)."""


def default_interpret() -> bool:
    """Compiled on TPU, interpret mode everywhere else."""
    return jax.default_backend() != "tpu"


def resolve_use_pallas(use_pallas: bool | None) -> bool:
    """The one place ``use_pallas=None`` is decided: the compiled Pallas
    kernels on TPU, the XLA path everywhere else (where Pallas would only
    run in interpret mode).  An explicit bool wins."""
    if use_pallas is None:
        return jax.default_backend() == "tpu"
    return bool(use_pallas)


def resolves_use_pallas(factory):
    """Resolve ``use_pallas`` (``resolve_use_pallas``) before the
    memoized ``factory`` is keyed, so a default and the bool it stands
    for share one cache entry and one compiled runner."""

    @functools.wraps(factory)
    def wrapper(*args, use_pallas: bool | None = None, **kwargs):
        return factory(*args, use_pallas=resolve_use_pallas(use_pallas),
                       **kwargs)

    return wrapper


def _warn_whole_strip(nz: int, cap: int, who: str) -> int:
    warnings.warn(
        f"{who}: nz={nz} has no usable strip divisor <= cap={cap}; "
        f"falling back to a SINGLE whole-height strip ({nz} rows "
        f"VMEM-resident). Fine for small grids; for tall grids pad nz "
        f"to a composite height or use the streamed kernel "
        f"(wave_block_stream_pallas), which refuses this fallback.",
        StripFallbackWarning,
        stacklevel=3,
    )
    return nz


def pick_bz(nz: int, cap: int = 128) -> int:
    """Largest divisor of nz ≤ cap, preferring (8,128)-aligned strips.

    Never returns a strip shorter than HALO — the kernel's clamped
    neighbor-row slices assume bz ≥ HALO, so a 1-row strip (e.g. prime
    nz > cap) would silently corrupt the stencil; such grids fall back
    to a single whole-height strip (with a ``StripFallbackWarning`` when
    that strip is taller than the cap — the whole field goes resident)."""
    aligned = [b for b in range(8, cap + 1, 8) if nz % b == 0]
    if aligned:
        return max(aligned)
    ok = [b for b in range(HALO, cap + 1) if nz % b == 0]
    if ok:
        return max(ok)
    return _warn_whole_strip(nz, cap, "pick_bz") if nz > cap else nz


def _shift_x(a, d: int, nx: int):
    """x-shift with zero boundary fill (shared by all stencil kernels).

    Operates on the LAST axis so the same helper serves the (win, NX)
    single-shot windows and the (S, win, NX) shot-batched ones."""
    ax = a.ndim - 1
    rolled = jnp.roll(a, d, axis=ax)
    idx = jax.lax.broadcasted_iota(jnp.int32, a.shape, ax)
    if d > 0:
        return jnp.where(idx >= d, rolled, 0.0)
    return jnp.where(idx < nx + d, rolled, 0.0)


def _wave_kernel(
    p_ref, p_prev_ref, v2dt2_ref, sponge_ref, p_next_ref, p_damped_ref,
    *, bz: int,
):
    i = pl.program_id(0)
    n = pl.num_programs(0)
    nz = p_ref.shape[0]
    nx = p_ref.shape[1]
    row0 = i * bz

    # one resident copy of p serves center AND both halo views
    center = p_ref[pl.ds(pl.multiple_of(row0, bz), bz), :]
    up = p_ref[pl.ds(jnp.maximum(row0 - HALO, 0), HALO), :]
    dn = p_ref[pl.ds(jnp.minimum(row0 + bz, nz - HALO), HALO), :]
    zero_h = jnp.zeros((HALO, nx), center.dtype)
    up = jnp.where(i == 0, zero_h, up)                 # physical boundary
    dn = jnp.where(i == n - 1, zero_h, dn)

    ext = jnp.concatenate([up, center, dn], axis=0)    # (bz+4, nx)

    # z-direction stencil from the extended strip
    lap = 2.0 * C0 * center
    lap += C1 * (ext[HALO - 1: HALO - 1 + bz, :]
                 + ext[HALO + 1: HALO + 1 + bz, :])
    lap += C2 * (ext[HALO - 2: HALO - 2 + bz, :]
                 + ext[HALO + 2: HALO + 2 + bz, :])

    # x-direction stencil with zero boundary fill (full width in-strip)
    lap += C1 * (_shift_x(center, 1, nx) + _shift_x(center, -1, nx))
    lap += C2 * (_shift_x(center, 2, nx) + _shift_x(center, -2, nx))

    sponge = sponge_ref[...]
    p_next = (2.0 * center - p_prev_ref[...] + v2dt2_ref[...] * lap) * sponge
    p_next_ref[...] = p_next
    p_damped_ref[...] = center * sponge


@functools.partial(jax.jit, static_argnames=("bz", "interpret"))
def wave_step_pallas(
    p: jax.Array,          # (NZ, NX) f32
    p_prev: jax.Array,
    v2dt2: jax.Array,
    sponge: jax.Array,
    *,
    bz: int | None = None,
    interpret: bool | None = None,
):
    nz, nx = p.shape
    if bz is None:
        bz = pick_bz(nz)
    if interpret is None:
        interpret = default_interpret()
    assert nz % bz == 0, (nz, bz)
    assert bz >= HALO, (bz, HALO)   # clamped halo slices need bz >= HALO
    grid = (nz // bz,)
    whole = pl.BlockSpec((nz, nx), lambda i: (0, 0))   # fetched once
    strip = pl.BlockSpec((bz, nx), lambda i: (i, 0))
    out_shape = [
        jax.ShapeDtypeStruct((nz, nx), p.dtype),
        jax.ShapeDtypeStruct((nz, nx), p.dtype),
    ]
    return pl.pallas_call(
        functools.partial(_wave_kernel, bz=bz),
        grid=grid,
        in_specs=[whole, strip, strip, strip],
        out_specs=[strip, strip],
        out_shape=out_shape,
        interpret=interpret,
    )(p, p_prev, v2dt2, sponge)


def pick_bz_block(nz: int, k: int, cap: int = 128) -> int:
    """Strip height for the k-step ``wave_block`` kernel.

    Largest divisor of nz ≤ cap (preferring 8-aligned strips) whose
    trapezoidal window ``bz + 2·k·HALO`` still fits inside the field;
    grids too short for any multi-strip trapezoid fall back to a single
    whole-height strip (window == field, both edges physical), warning
    via ``StripFallbackWarning`` when the fallback strip exceeds the cap
    (tall grid going whole-field resident — the streamed path raises
    instead, see ``pick_bz_stream``)."""
    pad = 2 * k * HALO
    aligned = [b for b in range(8, cap + 1, 8)
               if nz % b == 0 and b + pad <= nz]
    if aligned:
        return max(aligned)
    ok = [b for b in range(2, cap + 1) if nz % b == 0 and b + pad <= nz]
    if ok:
        return max(ok)
    # no multi-row strip fits (e.g. prime nz): one whole-height strip
    # beats a degenerate 1-row tiling that recomputes the window nz times
    return _warn_whole_strip(nz, cap, "pick_bz_block") if nz > cap else nz


def _lanes(nx: int) -> int:
    """Row width as VMEM holds it: rows are padded to whole 128-lane
    tiles, so a 24-column window costs as much as a 128-column one."""
    return -(-nx // 128) * 128


def resident_vmem_bytes(nz: int, nx: int, k: int = 1,
                        bz: int | None = None, s: int = 1) -> int:
    """VMEM footprint of the RESIDENT (whole-array BlockSpec) design:
    ``2·s`` whole (NZ, NX) f32 wavefields plus the TWO shared model
    fields fetched once, the pipeline's double-buffered output strips
    (per shot) and the trace block, every row lane-padded (``_lanes``).
    ``s`` is the shot-batch size — the model-field term is charged ONCE
    regardless of ``s`` (DESIGN.md §17); ``s=1`` reduces to the classic
    single-shot accounting."""
    bz = min(bz if bz is not None else 128, nz)
    nx = _lanes(nx)
    return 4 * ((2 * s + 2) * nz * nx + 2 * 2 * s * bz * nx + s * k * nx)


def stream_vmem_bytes(nz: int, nx: int, bz: int, k: int, s: int = 1) -> int:
    """VMEM footprint of the STREAMED design: two DMA slots of
    ``2·s + 2`` (win, NX) haloed f32 windows (``2·s`` shot-tiled
    wavefield windows + ONE shared pair of model-field windows), the
    double-buffered output strips, and the trace block — O(s·bz·NX),
    independent of NZ, rows lane-padded (``_lanes``).  ``s=1`` reduces
    to the classic accounting."""
    win = min(bz + 2 * k * HALO, nz)
    nx = _lanes(nx)
    return 4 * (2 * (2 * s + 2) * win * nx + 2 * 2 * s * bz * nx
                + s * k * nx)


def should_stream(nz: int, nx: int, k: int = 1,
                  vmem_budget: int | None = None, s: int = 1) -> bool:
    """True when the whole-array resident design would not fit the VMEM
    budget — the auto-dispatch rule ``ops.wave_block`` applies."""
    budget = vmem_budget if vmem_budget is not None else DEFAULT_VMEM_BUDGET
    return resident_vmem_bytes(nz, nx, k, s=s) > budget


def pick_bz_stream(nz: int, nx: int, k: int, *,
                   vmem_budget: int | None = None, cap: int = 512,
                   s: int = 1) -> int:
    """Strip height for the STREAMED k-step kernel under a VMEM budget.

    Largest 8-aligned divisor of nz ≤ cap whose double-buffered haloed
    windows fit ``vmem_budget`` (falling back to unaligned divisors ≥ 2
    before giving up).  Unlike ``pick_bz_block`` there is NO whole-height
    fallback: a strip that cannot be streamed within the budget raises —
    the silent blow-the-budget path is exactly the footgun the streamed
    design exists to remove.  ``s`` sizes the shot-batched variant's
    windows (``stream_vmem_bytes(..., s=s)``)."""
    budget = vmem_budget if vmem_budget is not None else DEFAULT_VMEM_BUDGET

    def fits(b: int) -> bool:
        return (nz % b == 0 and b + 2 * k * HALO <= nz
                and stream_vmem_bytes(nz, nx, b, k, s=s) <= budget)

    aligned = [b for b in range(8, min(cap, nz) + 1, 8) if fits(b)]
    if aligned:
        return max(aligned)
    ok = [b for b in range(2, min(cap, nz) + 1) if fits(b)]
    if ok:
        return max(ok)
    raise ValueError(
        f"no streamable strip height for nz={nz}, nx={nx}, k={k} under "
        f"vmem_budget={budget}: either nz has no divisor whose "
        f"(bz + {2 * k * HALO}, {nx}) double-buffered windows fit the "
        f"budget, or the grid is too short for a k={k} trapezoid. "
        f"Lower k, pad nz to a composite height, or raise the budget."
    )


def pick_k(nz: int, cap: int = 8) -> int:
    """Heuristic fused-block length to pair with ``pick_bz_block``.

    Largest power-of-two ≤ cap whose trapezoid still admits a
    multi-strip tiling of nz; degenerate (short) grids get whatever cap
    allows — a single whole-height strip handles any k."""
    k = cap
    while k > 1 and pick_bz_block(nz, k) == nz and nz > 2 * k * HALO:
        k //= 2
    return max(k, 1)


def _trapezoid_k_steps(
    cur, prevd, vw, sw, srcv_ref, srcp_ref, tr_ref,
    *, start, row0, win: int, nx: int, bz: int, k: int, rrow: int,
):
    """k fused leapfrog steps on one (win, NX) haloed window.

    The shared trapezoid body of BOTH block kernels (resident and
    streamed): per inner step, zero-extend in z, 4th-order Laplacian
    (z-rings from the extension, x-rings via ``_shift_x``), leapfrog +
    sponge, iota-masked source injection, and receiver-row capture into
    ``tr_ref`` for the program owning the receiver strip.  Returns the
    updated (cur, prevd) window pair."""
    zi = srcp_ref[0, 0]
    xi = srcp_ref[0, 1]
    iz = jax.lax.broadcasted_iota(jnp.int32, (win, nx), 0)
    ix = jax.lax.broadcasted_iota(jnp.int32, (win, nx), 1)
    zero_h = jnp.zeros((HALO, nx), cur.dtype)
    own_receiver = (rrow >= row0) & (rrow < row0 + bz)

    for j in range(k):
        ext = jnp.concatenate([zero_h, cur, zero_h], axis=0)
        lap = 2.0 * C0 * cur
        lap += C1 * (ext[HALO - 1: HALO - 1 + win, :]
                     + ext[HALO + 1: HALO + 1 + win, :])
        lap += C2 * (ext[HALO - 2: HALO - 2 + win, :]
                     + ext[HALO + 2: HALO + 2 + win, :])
        lap += C1 * (_shift_x(cur, 1, nx) + _shift_x(cur, -1, nx))
        lap += C2 * (_shift_x(cur, 2, nx) + _shift_x(cur, -2, nx))
        pn = (2.0 * cur - prevd + vw * lap) * sw
        # epilogue: source injection + receiver-row capture, fused
        pn = pn + jnp.where(
            (iz == zi - start) & (ix == xi), srcv_ref[0, j], 0.0
        )

        @pl.when(own_receiver)
        def _capture(pn=pn, j=j):
            tr_ref[j, :] = jax.lax.dynamic_slice_in_dim(
                pn, rrow - start, 1, axis=0
            )[0, :]

        prevd = cur * sw
        cur = pn
    return cur, prevd


def _wave_block_kernel(
    p_ref, pp_ref, v2dt2_ref, sponge_ref, srcv_ref, srcp_ref,
    p_out_ref, pp_out_ref, tr_ref,
    *, bz: int, win: int, k: int, rrow: int,
):
    """k fused timesteps on one z-strip (ghost-zone temporal blocking).

    Each program owns a (bz, NX) strip but computes on a (win, NX)
    window, ``win = bz + 2·k·HALO`` clamped to NZ, sliced out of the
    single VMEM-resident copy of each field.  Every inner step
    zero-extends the window in z: at a physical domain edge that IS the
    boundary condition; at an interior window edge it seeds a wrong
    value whose influence creeps inward HALO rows per step — after k
    steps exactly the owned strip is clean (the window start is clamped
    so the strip sits ≥ k·HALO rows from any interior window edge).
    Source injection, sponge damping and the receiver-row capture run in
    the step epilogue, so k launches and 2k wavefield HBM round-trips
    collapse into one pallas_call (DESIGN.md §13)."""
    i = pl.program_id(0)
    nz = p_ref.shape[0]
    nx = p_ref.shape[1]
    row0 = i * bz
    start = jnp.clip(row0 - k * HALO, 0, nz - win)
    off = row0 - start          # strip offset inside the window

    cur = p_ref[pl.ds(start, win), :]
    prevd = pp_ref[pl.ds(start, win), :]      # already sponge-damped
    vw = v2dt2_ref[pl.ds(start, win), :]
    sw = sponge_ref[pl.ds(start, win), :]
    cur, prevd = _trapezoid_k_steps(
        cur, prevd, vw, sw, srcv_ref, srcp_ref, tr_ref,
        start=start, row0=row0, win=win, nx=nx, bz=bz, k=k, rrow=rrow,
    )

    p_out_ref[...] = jax.lax.dynamic_slice_in_dim(cur, off, bz, axis=0)
    pp_out_ref[...] = jax.lax.dynamic_slice_in_dim(prevd, off, bz, axis=0)


@functools.partial(
    jax.jit, static_argnames=("bz", "receiver_row", "interpret")
)
def wave_block_pallas(
    p: jax.Array,          # (NZ, NX) f32
    p_prev: jax.Array,     # (NZ, NX), already sponge-damped
    v2dt2: jax.Array,
    sponge: jax.Array,
    src_vals: jax.Array,   # (k,) source amplitude per inner step
    src_z,                 # scalar int source row
    src_x,                 # scalar int source column
    *,
    receiver_row: int = 0,
    bz: int | None = None,
    interpret: bool | None = None,
):
    """k fused timesteps in ONE pallas_call (k = src_vals.shape[0]).

    Returns (p_k, p_prev_damped_k, traces (k, NX)).  Matches
    ``wave_block_ref`` to stencil-reorder tolerance (the z/x accumulation
    order differs from the reference — documented `allclose`, not
    bitwise; the pure-XLA block path carries the bitwise contract)."""
    nz, nx = p.shape
    k = int(src_vals.shape[0])
    if bz is None:
        bz = pick_bz_block(nz, k)
    if interpret is None:
        interpret = default_interpret()
    win = min(bz + 2 * k * HALO, nz)
    assert nz % bz == 0, (nz, bz)
    # reject oversized explicit strips: a bz < nz whose trapezoid spills
    # past the field would make every program recompute the WHOLE field
    # (grid-fold redundant work); only the single whole-height strip may
    # clamp the window
    assert bz == nz or bz + 2 * k * HALO <= nz, (nz, bz, k)
    grid = (nz // bz,)
    whole = pl.BlockSpec((nz, nx), lambda i: (0, 0))   # fetched once
    strip = pl.BlockSpec((bz, nx), lambda i: (i, 0))
    srcv = src_vals.reshape(1, k).astype(p.dtype)
    srcp = jnp.stack(
        [jnp.asarray(src_z, jnp.int32), jnp.asarray(src_x, jnp.int32)]
    ).reshape(1, 2)
    out_shape = [
        jax.ShapeDtypeStruct((nz, nx), p.dtype),
        jax.ShapeDtypeStruct((nz, nx), p.dtype),
        jax.ShapeDtypeStruct((k, nx), p.dtype),
    ]
    return pl.pallas_call(
        functools.partial(
            _wave_block_kernel, bz=bz, win=win, k=k,
            rrow=int(receiver_row),
        ),
        grid=grid,
        in_specs=[whole, whole, whole, whole,
                  pl.BlockSpec((1, k), lambda i: (0, 0)),
                  pl.BlockSpec((1, 2), lambda i: (0, 0))],
        out_specs=[strip, strip, pl.BlockSpec((k, nx), lambda i: (0, 0))],
        out_shape=out_shape,
        interpret=interpret,
    )(p, p_prev, v2dt2, sponge, srcv, srcp)


def _wave_block_stream_kernel(
    p_hbm, pp_hbm, v_hbm, s_hbm, srcv_ref, srcp_ref,
    p_out_ref, pp_out_ref, tr_ref, win_buf, sems,
    *, bz: int, win: int, k: int, rrow: int,
):
    """STREAMED k-step trapezoid: manual double-buffered window DMA.

    The four fields stay in HBM (``memory_space=ANY``); each grid step
    owns a (bz, NX) strip and computes on a (win, NX) haloed window that
    it DMAs into one of two VMEM slots.  Grid step i starts the fetch of
    strip i+1's window into the OTHER slot before waiting on its own, so
    the next window flies over this strip's k-step compute — the manual
    analogue of the pipelined-BlockSpec prefetch the resident kernel
    gets for free, without requiring the whole field to fit in VMEM
    (DESIGN.md §15).  Trapezoid math is ``_trapezoid_k_steps``, shared
    with the resident kernel."""
    i = pl.program_id(0)
    n = pl.num_programs(0)
    nz = p_hbm.shape[0]
    nx = p_hbm.shape[1]
    fields = (p_hbm, pp_hbm, v_hbm, s_hbm)

    def win_start(strip):
        return jnp.clip(strip * bz - k * HALO, 0, nz - win)

    def dma(slot, strip):
        start = win_start(strip)
        return [
            pltpu.make_async_copy(
                f.at[pl.ds(start, win), :],
                win_buf.at[slot, fi],
                sems.at[slot, fi],
            )
            for fi, f in enumerate(fields)
        ]

    @pl.when(i == 0)                 # warm-up: fetch our own window
    def _warmup():
        for c in dma(0, 0):
            c.start()

    @pl.when(i + 1 < n)              # prefetch next strip's window
    def _prefetch():
        for c in dma((i + 1) % 2, i + 1):
            c.start()

    slot = i % 2
    for c in dma(slot, i):           # wait for our window to land
        c.wait()

    row0 = i * bz
    start = win_start(i)
    off = row0 - start               # strip offset inside the window
    cur, prevd = _trapezoid_k_steps(
        win_buf[slot, 0], win_buf[slot, 1],
        win_buf[slot, 2], win_buf[slot, 3],
        srcv_ref, srcp_ref, tr_ref,
        start=start, row0=row0, win=win, nx=nx, bz=bz, k=k, rrow=rrow,
    )
    p_out_ref[...] = jax.lax.dynamic_slice_in_dim(cur, off, bz, axis=0)
    pp_out_ref[...] = jax.lax.dynamic_slice_in_dim(prevd, off, bz, axis=0)


@functools.partial(
    jax.jit,
    static_argnames=("receiver_row", "bz", "interpret", "vmem_budget"),
)
def wave_block_stream_pallas(
    p: jax.Array,          # (NZ, NX) f32
    p_prev: jax.Array,     # (NZ, NX), already sponge-damped
    v2dt2: jax.Array,
    sponge: jax.Array,
    src_vals: jax.Array,   # (k,) source amplitude per inner step
    src_z,                 # scalar int source row
    src_x,                 # scalar int source column
    *,
    receiver_row: int = 0,
    bz: int | None = None,
    interpret: bool | None = None,
    vmem_budget: int | None = None,
):
    """k fused timesteps, STREAMED: VMEM holds two haloed windows, not
    the field (k = src_vals.shape[0]).

    The production-scale form of ``wave_block_pallas``: fields live in
    HBM and each grid step double-buffer-DMAs its (bz + 2·k·HALO, NX)
    window while the previous strip computes, so capacity is O(bz·NX)
    — a 4096² grid (256 MB resident) streams in ~8 MB of VMEM.  Strip
    height defaults to ``pick_bz_stream`` (raises rather than fall back
    to a whole-height resident strip).  Returns
    (p_k, p_prev_damped_k, traces (k, NX)); same accuracy contract as
    the resident Pallas kernel (allclose vs ``wave_block_ref``; the
    bitwise strip-tiled oracle is ``ref.wave_block_strips_ref``)."""
    nz, nx = p.shape
    k = int(src_vals.shape[0])
    if interpret is None:
        interpret = default_interpret()
    if bz is None:
        bz = pick_bz_stream(nz, nx, k, vmem_budget=vmem_budget)
    budget = vmem_budget if vmem_budget is not None else DEFAULT_VMEM_BUDGET
    win = bz + 2 * k * HALO
    assert nz % bz == 0, (nz, bz)
    assert win <= nz, (nz, bz, k)    # no whole-height fallback, ever
    assert stream_vmem_bytes(nz, nx, bz, k) <= budget, (nz, nx, bz, k)
    grid = (nz // bz,)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    strip = pl.BlockSpec((bz, nx), lambda i: (i, 0))
    srcv = src_vals.reshape(1, k).astype(p.dtype)
    srcp = jnp.stack(
        [jnp.asarray(src_z, jnp.int32), jnp.asarray(src_x, jnp.int32)]
    ).reshape(1, 2)
    out_shape = [
        jax.ShapeDtypeStruct((nz, nx), p.dtype),
        jax.ShapeDtypeStruct((nz, nx), p.dtype),
        jax.ShapeDtypeStruct((k, nx), p.dtype),
    ]
    kwargs = {}
    if not interpret:
        # enforce the budget at compile time on real TPUs (twice over:
        # the trapezoid's temporaries come on top of the buffers the
        # budget counts); interpret mode has no VMEM, the assert above
        # carries the contract
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=2 * budget
        )
    return pl.pallas_call(
        functools.partial(
            _wave_block_stream_kernel, bz=bz, win=win, k=k,
            rrow=int(receiver_row),
        ),
        grid=grid,
        in_specs=[hbm, hbm, hbm, hbm,
                  pl.BlockSpec((1, k), lambda i: (0, 0)),
                  pl.BlockSpec((1, 2), lambda i: (0, 0))],
        out_specs=[strip, strip, pl.BlockSpec((k, nx), lambda i: (0, 0))],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((2, 4, win, nx), p.dtype),
            pltpu.SemaphoreType.DMA((2, 4)),
        ],
        interpret=interpret,
        **kwargs,
    )(p, p_prev, v2dt2, sponge, srcv, srcp)


def _strip_starts(nz: int, bz: int, win: int, k: int) -> list[int]:
    """First window row of every strip (static): the strip's rows less
    k·HALO, clamped so the window stays inside the field."""
    return [min(max(i * bz - k * HALO, 0), nz - win)
            for i in range(nz // bz)]


def _rows_aligned(nz: int, bz: int, win: int, k: int) -> bool:
    """True when every window starts on an 8-row tile, so Mosaic can
    prove a dynamic ``pl.ds(start, win)`` row slice aligned."""
    return all(s % 8 == 0 for s in _strip_starts(nz, bz, win, k))


def _check_compiled_geometry(nz: int, nx: int, bz: int, win: int, k: int,
                             *, stream: bool) -> None:
    """Refuse, before Mosaic does, a strip geometry the TPU compiler
    cannot lower: strips must be 8-row tiles (or the whole height), and
    every window must start on an 8-row tile — which holds when
    k·HALO and bz are multiples of 8 (k = 4, 8, ...).  The streamed
    kernel DMAs windows out of HBM, which also needs 8-row windows and
    128-lane rows."""
    bad = []
    if bz % 8 and bz != nz:
        bad.append(f"bz={bz} is not a multiple of 8")
    if win != nz and not _rows_aligned(nz, bz, win, k):
        bad.append(f"window starts are not 8-row aligned (k·HALO="
                   f"{k * HALO}, bz={bz}, nz={nz})")
    if stream and win % 8:
        bad.append(f"window height {win} is not a multiple of 8")
    if stream and nx % 128:
        bad.append(f"nx={nx} is not a multiple of 128")
    if bad:
        raise ValueError("compiled stencil kernel: " + "; ".join(bad))


def _receiver_window_row(rrow: int, nz: int, bz: int, win: int,
                         k: int) -> tuple[int, int] | None:
    """(strip that owns the receiver row, that row's index inside the
    strip's window), or None when the row is outside the field."""
    if not 0 <= rrow < nz:
        return None
    owner = rrow // bz
    return owner, rrow - _strip_starts(nz, bz, win, k)[owner]


def _store_strip(out_ref, window, row0, start, *, nz: int, bz: int,
                 win: int, k: int):
    """Store the owned rows of a (S, win, NX) window value — the bz rows
    from ``row0 - start`` — into ``out_ref``.  That offset is traced but
    takes only the few static values ``_strip_starts`` allows, so each
    is one branch with a static slice (the TPU has no dynamic row slice
    of a value)."""
    offsets = {i * bz - s for i, s in enumerate(_strip_starts(nz, bz, win, k))}
    for o in sorted(offsets):
        @pl.when(row0 - start == o)
        def _store(o=o):
            out_ref[...] = window[:, o: o + bz, :]


def _trapezoid_k_steps_shots(
    cur, prevd, vw, sw, srcv_ref, srcz_ref, srcx_ref, tr_ref,
    *, start, i, win: int, nx: int, k: int, recv,
):
    """k fused leapfrog steps on an (S, win, NX) shot-batched window.

    The shared trapezoid body of BOTH batched block kernels (resident
    and streamed): per inner step, zero-extend in z, 4th-order Laplacian
    (z-rings from the extension, x-rings via ``_shift_x``), leapfrog +
    sponge, per-shot source injection, and receiver-row capture into
    ``tr_ref`` by the program owning the receiver strip.  The model
    windows ``vw``/``sw`` stay 2-D — (win, NX) — and broadcast across
    shots, so the model fields are read once per strip no matter how
    many shots ride the batch (DESIGN.md §17).  ``srcz_ref``/``srcx_ref``
    are (S, 1, 1) int32 source rows/columns, ``srcv_ref`` is (S, 1, k)
    amplitudes; ``recv`` is ``_receiver_window_row``'s answer."""
    ns = cur.shape[0]
    iz = jax.lax.broadcasted_iota(jnp.int32, (ns, win, nx), 1)
    ix = jax.lax.broadcasted_iota(jnp.int32, (ns, win, nx), 2)
    # each shot's source point, fixed across the k steps
    hit = (iz == srcz_ref[...] - start) & (ix == srcx_ref[...])
    zero_h = jnp.zeros((ns, HALO, nx), cur.dtype)

    for j in range(k):
        ext = jnp.concatenate([zero_h, cur, zero_h], axis=1)
        lap = 2.0 * C0 * cur
        lap += C1 * (ext[:, HALO - 1: HALO - 1 + win, :]
                     + ext[:, HALO + 1: HALO + 1 + win, :])
        lap += C2 * (ext[:, HALO - 2: HALO - 2 + win, :]
                     + ext[:, HALO + 2: HALO + 2 + win, :])
        lap += C1 * (_shift_x(cur, 1, nx) + _shift_x(cur, -1, nx))
        lap += C2 * (_shift_x(cur, 2, nx) + _shift_x(cur, -2, nx))
        pn = (2.0 * cur - prevd + vw * lap) * sw
        # epilogue: per-shot source injection + receiver-row capture
        pn = pn + jnp.where(hit, srcv_ref[:, :, j: j + 1], 0.0)

        if recv is not None:
            owner, r = recv

            @pl.when(i == owner)
            def _capture(pn=pn, j=j, r=r):
                tr_ref[:, j: j + 1, :] = pn[:, r: r + 1, :]

        prevd = cur * sw
        cur = pn
    return cur, prevd


def _wave_block_shots_kernel(
    p_ref, pp_ref, v2dt2_ref, sponge_ref, srcv_ref, srcz_ref, srcx_ref,
    p_out_ref, pp_out_ref, tr_ref,
    *, bz: int, win: int, k: int, rrow: int,
):
    """Shot-batched k-step trapezoid (ghost-zone temporal blocking):
    each program owns an (S, bz, NX) strip and computes on (S, win, NX)
    windows, ``win = bz + 2·k·HALO`` clamped to NZ, sliced from the
    resident wavefields, while the model fields stay 2-D and are sliced
    ONCE per strip for all shots.  Every inner step zero-extends the
    window in z: at a physical edge that IS the boundary condition; at
    an interior window edge the wrong value creeps inward HALO rows per
    step, so after k steps exactly the owned strip is clean.  Source
    injection, sponge damping and receiver capture run in the step
    epilogue, so k launches collapse into one pallas_call
    (DESIGN.md §13)."""
    i = pl.program_id(0)
    nz = p_ref.shape[1]
    nx = p_ref.shape[2]
    row0 = i * bz
    start = jnp.clip(row0 - k * HALO, 0, nz - win)
    if _rows_aligned(nz, bz, win, k):
        start = pl.multiple_of(start, 8)

    cur = p_ref[:, pl.ds(start, win), :]
    prevd = pp_ref[:, pl.ds(start, win), :]   # already sponge-damped
    vw = v2dt2_ref[pl.ds(start, win), :]      # shared across shots
    sw = sponge_ref[pl.ds(start, win), :]
    cur, prevd = _trapezoid_k_steps_shots(
        cur, prevd, vw, sw, srcv_ref, srcz_ref, srcx_ref, tr_ref,
        start=start, i=i, win=win, nx=nx, k=k,
        recv=_receiver_window_row(rrow, nz, bz, win, k),
    )
    geometry = dict(nz=nz, bz=bz, win=win, k=k)
    _store_strip(p_out_ref, cur, row0, start, **geometry)
    _store_strip(pp_out_ref, prevd, row0, start, **geometry)


def _norm_src_shots(src_vals, src_z, src_x, ns: int, dtype):
    """Normalize batched source args for the kernels: (k,)-or-(S, k)
    amplitudes to an (S, 1, k) block, per-shot positions to (S, 1, 1)
    int32 blocks (last two dims whole, so each is one VMEM tile)."""
    srcv = jnp.asarray(src_vals, dtype)
    if srcv.ndim == 1:
        srcv = jnp.broadcast_to(srcv, (ns, srcv.shape[0]))
    srcz = jnp.broadcast_to(jnp.asarray(src_z, jnp.int32), (ns,))
    srcx = jnp.broadcast_to(jnp.asarray(src_x, jnp.int32), (ns,))
    return (srcv.reshape(ns, 1, -1), srcz.reshape(ns, 1, 1),
            srcx.reshape(ns, 1, 1))


@functools.partial(
    jax.jit, static_argnames=("bz", "receiver_row", "interpret")
)
def wave_block_shots_pallas(
    p: jax.Array,          # (S, NZ, NX) f32 shot batch
    p_prev: jax.Array,     # (S, NZ, NX), already sponge-damped
    v2dt2: jax.Array,      # (NZ, NX) shared model field
    sponge: jax.Array,     # (NZ, NX) shared model field
    src_vals: jax.Array,   # (k,) shared or (S, k) per-shot amplitudes
    src_z,                 # (S,) int per-shot source rows
    src_x,                 # (S,) int per-shot source columns
    *,
    receiver_row: int = 0,
    bz: int | None = None,
    interpret: bool | None = None,
):
    """Shot-batched ``wave_block_pallas``: k fused timesteps for ALL S
    shots in ONE pallas_call.

    One grid pass covers the whole batch — the model fields are fetched
    once (not once per shot) and every strip's trapezoid is computed for
    all shots together, so kernel launches and model-field HBM traffic
    are amortized S-fold vs ``vmap``-of-``wave_block_pallas``
    (DESIGN.md §17).  Returns (p_k (S, NZ, NX), p_prev_damped_k,
    traces (S, k, NX)); the S=1 batch is bitwise-equal to the 2-D
    kernel (pinned by tests)."""
    ns, nz, nx = p.shape
    k = int(src_vals.shape[-1])
    if bz is None:
        bz = pick_bz_block(nz, k)
    if interpret is None:
        interpret = default_interpret()
    win = min(bz + 2 * k * HALO, nz)
    assert nz % bz == 0, (nz, bz)
    assert bz == nz or bz + 2 * k * HALO <= nz, (nz, bz, k)
    if not interpret:
        _check_compiled_geometry(nz, nx, bz, win, k, stream=False)
    grid = (nz // bz,)
    whole3 = pl.BlockSpec((ns, nz, nx), lambda i: (0, 0, 0))  # fetched once
    whole2 = pl.BlockSpec((nz, nx), lambda i: (0, 0))         # model fields
    strip3 = pl.BlockSpec((ns, bz, nx), lambda i: (0, i, 0))
    src = pl.BlockSpec((ns, 1, 1), lambda i: (0, 0, 0))
    srcv, srcz, srcx = _norm_src_shots(src_vals, src_z, src_x, ns, p.dtype)
    out_shape = [
        jax.ShapeDtypeStruct((ns, nz, nx), p.dtype),
        jax.ShapeDtypeStruct((ns, nz, nx), p.dtype),
        jax.ShapeDtypeStruct((ns, k, nx), p.dtype),
    ]
    return pl.pallas_call(
        functools.partial(
            _wave_block_shots_kernel, bz=bz, win=win, k=k,
            rrow=int(receiver_row),
        ),
        grid=grid,
        in_specs=[whole3, whole3, whole2, whole2,
                  pl.BlockSpec((ns, 1, k), lambda i: (0, 0, 0)), src, src],
        out_specs=[strip3, strip3,
                   pl.BlockSpec((ns, k, nx), lambda i: (0, 0, 0))],
        out_shape=out_shape,
        interpret=interpret,
    )(p, p_prev, v2dt2, sponge, srcv, srcz, srcx)


def _wave_block_shots_stream_kernel(
    p_hbm, pp_hbm, v_hbm, s_hbm, srcv_ref, srcz_ref, srcx_ref,
    p_out_ref, pp_out_ref, tr_ref, fwin_buf, mwin_buf, fsems, msems,
    *, bz: int, win: int, k: int, rrow: int,
):
    """Shot-batched STREAMED trapezoid: double-buffered window DMA with
    a shot-tiled wavefield slot and a SINGLE model-field slot.

    The wavefields stay in HBM as (S, NZ, NX); each grid step DMAs an
    (S, win, NX) window pair into one of two VMEM slots.  The model
    fields get their own (2, 2, win, NX) scratch — one (win, NX) window
    per field per slot, DMA'd ONCE per strip and reused by every shot
    in the batch, which is exactly the traffic the shot batch exists to
    amortize (DESIGN.md §17).  Grid step i starts strip i+1's fetch
    into the other slot before waiting on its own, so the next window
    flies over this strip's k-step compute (DESIGN.md §15); the
    trapezoid math is ``_trapezoid_k_steps_shots``, shared with the
    resident kernel."""
    i = pl.program_id(0)
    n = pl.num_programs(0)
    nz = p_hbm.shape[1]
    nx = p_hbm.shape[2]
    aligned = _rows_aligned(nz, bz, win, k)

    def win_start(strip):
        start = jnp.clip(strip * bz - k * HALO, 0, nz - win)
        return pl.multiple_of(start, 8) if aligned else start

    def dma(slot, strip):
        start = win_start(strip)
        copies = [
            pltpu.make_async_copy(
                f.at[:, pl.ds(start, win), :],
                fwin_buf.at[slot, fi],
                fsems.at[slot, fi],
            )
            for fi, f in enumerate((p_hbm, pp_hbm))
        ]
        copies += [
            pltpu.make_async_copy(
                f.at[pl.ds(start, win), :],
                mwin_buf.at[slot, fi],
                msems.at[slot, fi],
            )
            for fi, f in enumerate((v_hbm, s_hbm))
        ]
        return copies

    @pl.when(i == 0)                 # warm-up: fetch our own window
    def _warmup():
        for c in dma(0, 0):
            c.start()

    @pl.when(i + 1 < n)              # prefetch next strip's window
    def _prefetch():
        for c in dma((i + 1) % 2, i + 1):
            c.start()

    slot = i % 2
    for c in dma(slot, i):           # wait for our window to land
        c.wait()

    row0 = i * bz
    start = win_start(i)
    cur, prevd = _trapezoid_k_steps_shots(
        fwin_buf[slot, 0], fwin_buf[slot, 1],
        mwin_buf[slot, 0], mwin_buf[slot, 1],
        srcv_ref, srcz_ref, srcx_ref, tr_ref,
        start=start, i=i, win=win, nx=nx, k=k,
        recv=_receiver_window_row(rrow, nz, bz, win, k),
    )
    geometry = dict(nz=nz, bz=bz, win=win, k=k)
    _store_strip(p_out_ref, cur, row0, start, **geometry)
    _store_strip(pp_out_ref, prevd, row0, start, **geometry)


@functools.partial(
    jax.jit,
    static_argnames=("receiver_row", "bz", "interpret", "vmem_budget"),
)
def wave_block_shots_stream_pallas(
    p: jax.Array,          # (S, NZ, NX) f32 shot batch
    p_prev: jax.Array,     # (S, NZ, NX), already sponge-damped
    v2dt2: jax.Array,      # (NZ, NX) shared model field
    sponge: jax.Array,     # (NZ, NX) shared model field
    src_vals: jax.Array,   # (k,) shared or (S, k) per-shot amplitudes
    src_z,                 # (S,) int per-shot source rows
    src_x,                 # (S,) int per-shot source columns
    *,
    receiver_row: int = 0,
    bz: int | None = None,
    interpret: bool | None = None,
    vmem_budget: int | None = None,
):
    """Shot-batched ``wave_block_stream_pallas``: VMEM holds two
    (S, win, NX) wavefield window slots plus ONE shared (win, NX)
    model-field slot pair — capacity O(s·bz·NX), independent of NZ.

    Strip height defaults to ``pick_bz_stream(..., s=S)`` (raises
    rather than fall back to a whole-height resident strip — same
    no-fallback contract as the single-shot streamed kernel).  Returns
    (p_k, p_prev_damped_k, traces (S, k, NX))."""
    ns, nz, nx = p.shape
    k = int(src_vals.shape[-1])
    if interpret is None:
        interpret = default_interpret()
    if bz is None:
        bz = pick_bz_stream(nz, nx, k, vmem_budget=vmem_budget, s=ns)
    budget = vmem_budget if vmem_budget is not None else DEFAULT_VMEM_BUDGET
    win = bz + 2 * k * HALO
    assert nz % bz == 0, (nz, bz)
    assert win <= nz, (nz, bz, k)    # no whole-height fallback, ever
    assert stream_vmem_bytes(nz, nx, bz, k, s=ns) <= budget, \
        (nz, nx, bz, k, ns)
    grid = (nz // bz,)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    strip3 = pl.BlockSpec((ns, bz, nx), lambda i: (0, i, 0))
    src = pl.BlockSpec((ns, 1, 1), lambda i: (0, 0, 0))
    srcv, srcz, srcx = _norm_src_shots(src_vals, src_z, src_x, ns, p.dtype)
    out_shape = [
        jax.ShapeDtypeStruct((ns, nz, nx), p.dtype),
        jax.ShapeDtypeStruct((ns, nz, nx), p.dtype),
        jax.ShapeDtypeStruct((ns, k, nx), p.dtype),
    ]
    kwargs = {}
    if not interpret:
        _check_compiled_geometry(nz, nx, bz, win, k, stream=True)
        # the budget counts the buffers; the k-step trapezoid's
        # temporaries come on top (DESIGN.md §15)
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=2 * budget
        )
    return pl.pallas_call(
        functools.partial(
            _wave_block_shots_stream_kernel, bz=bz, win=win, k=k,
            rrow=int(receiver_row),
        ),
        grid=grid,
        in_specs=[hbm, hbm, hbm, hbm,
                  pl.BlockSpec((ns, 1, k), lambda i: (0, 0, 0)), src, src],
        out_specs=[strip3, strip3,
                   pl.BlockSpec((ns, k, nx), lambda i: (0, 0, 0))],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((2, 2, ns, win, nx), p.dtype),
            pltpu.VMEM((2, 2, win, nx), p.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        interpret=interpret,
        **kwargs,
    )(p, p_prev, v2dt2, sponge, srcv, srcz, srcx)


def pick_shot_tile(n_shots: int, nz: int, nx: int, k: int, *,
                   bz: int | None = None, stream: bool = False,
                   vmem_budget: int | None = None) -> int:
    """Largest shot-tile ≤ ``n_shots`` whose batched design fits the
    VMEM budget — the default ``shot_tile`` the Pallas dispatch in
    ``ops.wave_block`` uses.

    Resident tiles are sized by ``resident_vmem_bytes(..., s=t)``,
    streamed tiles by the existence of a streamable strip at ``s=t``
    (``pick_bz_stream``).  Only divisors of ``n_shots`` are considered,
    so no tile is ever ragged by default (explicit ``shot_tile`` args
    may still be unaligned — the dispatch handles the remainder tile).
    Always ≥ 1: a single shot that cannot fit resident is the streamed
    path's problem (``should_stream``), not the tile picker's.  Tiles
    whose strip is a whole number of 8-row tiles win over larger ones
    whose strip is not: the compiled kernel needs such strips
    (``_check_compiled_geometry``)."""
    budget = vmem_budget if vmem_budget is not None else DEFAULT_VMEM_BUDGET

    def strip(t: int) -> int | None:
        """Strip height the tile runs at, None when it does not fit."""
        if stream:
            try:
                return pick_bz_stream(nz, nx, k, vmem_budget=budget, s=t)
            except ValueError:
                return None
        b = bz if bz is not None else pick_bz_block(nz, k)
        return b if resident_vmem_bytes(nz, nx, k, bz=b, s=t) <= budget \
            else None

    strips = {t: strip(t) for t in range(1, n_shots + 1) if n_shots % t == 0}
    ok = [t for t, b in strips.items() if b is not None]
    aligned = [t for t in ok if strips[t] % 8 == 0 or strips[t] == nz]
    return max(aligned or ok, default=1)


def _tune_backend(backend: str | None) -> str:
    return backend if backend is not None else jax.default_backend()


@functools.lru_cache(maxsize=None)
def _autotune_bz_cached(
    nz: int, nx: int, candidates: tuple[int, ...], repeats: int,
    backend: str,
) -> int:
    cands = [b for b in candidates if nz % b == 0]
    if not cands:
        return pick_bz(nz)
    key = jax.random.key(0)
    p = jax.random.normal(key, (nz, nx), jnp.float32)
    args = (p, p, jnp.full((nz, nx), 0.1, jnp.float32),
            jnp.ones((nz, nx), jnp.float32))
    best_bz, best_t = cands[0], float("inf")
    for b in cands:
        out = wave_step_pallas(*args, bz=b)       # compile
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(repeats):
            out = wave_step_pallas(*args, bz=b)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / repeats
        if dt < best_t:
            best_bz, best_t = b, dt
    return best_bz


def autotune_bz(
    nz: int, nx: int, candidates: tuple[int, ...] = (8, 16, 32, 64, 128),
    repeats: int = 3, backend: str | None = None,
) -> int:
    """Sweep strip heights on this backend, return the fastest.

    Wall-clock autotune over the real kernel (interpret mode off-TPU, so
    absolute numbers are NOT TPU projections — but the relative ranking
    tracks the tiling trade-off).  Memoized per (shape, candidates,
    backend): an FWISession rebuilt after RESHARD re-reads the cached
    choice instead of re-timing."""
    return _autotune_bz_cached(
        nz, nx, tuple(candidates), repeats, _tune_backend(backend)
    )


@functools.lru_cache(maxsize=None)
def _autotune_bz_k_cached(
    nz: int, nx: int, bz_candidates: tuple[int, ...],
    k_candidates: tuple[int, ...], repeats: int, backend: str,
) -> tuple[int, int]:
    key = jax.random.key(0)
    p = jax.random.normal(key, (nz, nx), jnp.float32)
    v = jnp.full((nz, nx), 0.1, jnp.float32)
    s = jnp.ones((nz, nx), jnp.float32)
    best, best_t = (pick_bz_block(nz, pick_k(nz)), pick_k(nz)), float("inf")
    for k in k_candidates:
        srcv = jnp.zeros((k,), jnp.float32)
        bzs = [b for b in bz_candidates
               if nz % b == 0 and (b + 2 * k * HALO <= nz or b == nz)]
        if not bzs:
            bzs = [pick_bz_block(nz, k)]
        for b in bzs:
            out = wave_block_pallas(p, p, v, s, srcv, 0, 0, bz=b)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(repeats):
                out = wave_block_pallas(p, p, v, s, srcv, 0, 0, bz=b)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / (repeats * k)   # per step
            if dt < best_t:
                best, best_t = (b, k), dt
    return best


@functools.lru_cache(maxsize=None)
def _autotune_stream_cached(
    nz: int, nx: int, bz_candidates: tuple[int, ...],
    k_candidates: tuple[int, ...], repeats: int, backend: str,
    budget: int,
) -> tuple[int, int]:
    key = jax.random.key(0)
    p = jax.random.normal(key, (nz, nx), jnp.float32)
    v = jnp.full((nz, nx), 0.1, jnp.float32)
    s = jnp.ones((nz, nx), jnp.float32)
    best, best_t = None, float("inf")
    for k in k_candidates:
        srcv = jnp.zeros((k,), jnp.float32)
        bzs = [b for b in bz_candidates
               if nz % b == 0 and b + 2 * k * HALO <= nz
               and stream_vmem_bytes(nz, nx, b, k) <= budget]
        if not bzs:
            try:
                bzs = [pick_bz_stream(nz, nx, k, vmem_budget=budget)]
            except ValueError:
                continue                      # no streamable strip at this k
        for b in bzs:
            out = wave_block_stream_pallas(
                p, p, v, s, srcv, 0, 0, bz=b, vmem_budget=budget
            )
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(repeats):
                out = wave_block_stream_pallas(
                    p, p, v, s, srcv, 0, 0, bz=b, vmem_budget=budget
                )
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / (repeats * k)   # per step
            if dt < best_t:
                best, best_t = (b, k), dt
    if best is None:
        raise ValueError(
            f"no (bz, k) candidate streams nz={nz}, nx={nx} under "
            f"vmem_budget={budget}"
        )
    return best


@functools.lru_cache(maxsize=None)
def _autotune_shots_cached(
    ns: int, nz: int, nx: int, bz_candidates: tuple[int, ...],
    k_candidates: tuple[int, ...], tile_candidates: tuple[int, ...],
    repeats: int, backend: str, stream: bool, budget: int,
) -> tuple[int, int, int]:
    key = jax.random.key(0)
    p = jax.random.normal(key, (ns, nz, nx), jnp.float32)
    v = jnp.full((nz, nx), 0.1, jnp.float32)
    s = jnp.ones((nz, nx), jnp.float32)
    sz = jnp.full((ns,), nz // 2, jnp.int32)
    sx = jnp.arange(ns, dtype=jnp.int32) % nx
    best, best_t = None, float("inf")
    for k in k_candidates:
        srcv = jnp.zeros((k,), jnp.float32)
        for t in tile_candidates:
            if not 1 <= t <= ns:
                continue
            if stream:
                bzs = [b for b in bz_candidates
                       if nz % b == 0 and b + 2 * k * HALO <= nz
                       and stream_vmem_bytes(nz, nx, b, k, s=t) <= budget]
                if not bzs:
                    try:
                        bzs = [pick_bz_stream(nz, nx, k,
                                              vmem_budget=budget, s=t)]
                    except ValueError:
                        continue          # no streamable strip at (k, t)
            else:
                bzs = [b for b in bz_candidates
                       if nz % b == 0
                       and (b + 2 * k * HALO <= nz or b == nz)
                       and resident_vmem_bytes(nz, nx, k, bz=b,
                                               s=t) <= budget]
                if not bzs:
                    continue              # tile blows the resident budget

            def run(b, t=t, srcv=srcv):
                outs = []
                for lo in range(0, ns, t):
                    hi = min(lo + t, ns)
                    if stream:
                        outs.append(wave_block_shots_stream_pallas(
                            p[lo:hi], p[lo:hi], v, s, srcv,
                            sz[lo:hi], sx[lo:hi], bz=b,
                            vmem_budget=budget,
                        ))
                    else:
                        outs.append(wave_block_shots_pallas(
                            p[lo:hi], p[lo:hi], v, s, srcv,
                            sz[lo:hi], sx[lo:hi], bz=b,
                        ))
                return outs

            for b in bzs:
                jax.block_until_ready(run(b))          # compile
                t0 = time.perf_counter()
                for _ in range(repeats):
                    out = run(b)
                jax.block_until_ready(out)
                # amortized per step per shot
                dt = (time.perf_counter() - t0) / (repeats * k * ns)
                if dt < best_t:
                    best, best_t = (b, k, t), dt
    if best is None:
        raise ValueError(
            f"no (bz, k, shot_tile) candidate fits ns={ns}, nz={nz}, "
            f"nx={nx} under vmem_budget={budget} (stream={stream})"
        )
    return best


def autotune_bz_k(
    nz: int, nx: int,
    bz_candidates: tuple[int, ...] = (8, 16, 24, 32, 40, 64, 120, 128),
    k_candidates: tuple[int, ...] = (1, 2, 4, 8),
    repeats: int = 3, backend: str | None = None,
    *, stream: bool | None = None, vmem_budget: int | None = None,
    n_shots: int | None = None,
    shot_tile_candidates: tuple[int, ...] | None = None,
):
    """Jointly tune (strip height, fused-block length) for ``wave_block``.

    Amortized per-STEP wall clock decides, so longer blocks only win
    when the extra trapezoid compute pays for the saved round trips.
    Memoized per (shape, candidates, backend) in-process — repeated
    ``FWISession`` rebuilds after a RESHARD reuse the cached pair
    instead of re-timing (DESIGN.md §13).

    ``stream`` switches the search to the STREAMED kernel's (strip,
    depth) space, where candidates must also fit ``vmem_budget``
    (``stream_vmem_bytes``); ``stream=None`` auto-selects via
    ``should_stream`` — grids whose resident design would blow the
    budget tune the streamed kernel (DESIGN.md §15).

    ``n_shots`` extends the search to the SHOT-BATCHED engine's
    ``(bz, k, shot_tile)`` space (DESIGN.md §17): candidates sweep the
    tile sizes in ``shot_tile_candidates`` (default: the divisors of
    ``n_shots``), each sized against the s-aware VMEM accounting, and
    the return value becomes a 3-tuple.  Without ``n_shots`` the
    classic 2-tuple ``(bz, k)`` is returned, so existing callers are
    unchanged."""
    budget = vmem_budget if vmem_budget is not None else DEFAULT_VMEM_BUDGET
    if stream is None:
        stream = should_stream(nz, nx, vmem_budget=budget)
    if n_shots is not None:
        if shot_tile_candidates is None:
            shot_tile_candidates = tuple(
                t for t in range(1, n_shots + 1) if n_shots % t == 0
            )
        return _autotune_shots_cached(
            n_shots, nz, nx, tuple(bz_candidates), tuple(k_candidates),
            tuple(shot_tile_candidates), repeats, _tune_backend(backend),
            bool(stream), budget,
        )
    if stream:
        return _autotune_stream_cached(
            nz, nx, tuple(bz_candidates), tuple(k_candidates), repeats,
            _tune_backend(backend), budget,
        )
    return _autotune_bz_k_cached(
        nz, nx, tuple(bz_candidates), tuple(k_candidates), repeats,
        _tune_backend(backend),
    )
