"""Pallas TPU kernels: k fused 4th-order wave-equation timesteps for a
batch of shots.

TPU adaptation of the paper's (CPU/MPI, Eigen-based) FWI hot loop —
re-blocked for the TPU memory hierarchy instead of ported.  One kernel
family, two designs, both shot-batched — (S, NZ, NX) wavefields over
shared (NZ, NX) model fields; one shot is the batch S=1:

* ``wave_block_shots_pallas`` — RESIDENT: each grid step owns an
  (S, bz, NX) strip and computes on its (S, bz + 2·k·HALO, NX) haloed
  window, sliced out of whole-array VMEM copies the pipeline fetches
  once (constant index maps).  Strips span the full width, so x needs
  no halo — the paper's striped partitioning.  The whole field sits in
  VMEM, which caps the design at ~1k²-class grids.
* ``wave_block_shots_stream_pallas`` — STREAMED (DESIGN.md §15): the
  fields stay in HBM and each grid step double-buffer-DMAs its haloed
  window while the previous strip computes, walking the shot tiles in
  its own grid — ``stream_vmem_bytes`` is O(tile·bz·NX), independent
  of NZ and of S.

Both run one trapezoid body (``_trapezoid_k_steps_shots``): per inner
step Laplacian + leapfrog + sponge damping, source injection and
receiver capture fused in the epilogue, so k steps cost one launch and
one wavefield HBM round trip (DESIGN.md §13).  Window edges zero-extend
every step: at a physical edge that is ref.py's zero-halo convention,
at an interior one the wrong values stay outside the owned strip.  The
model-field windows are read once per strip for every shot of the tile
(DESIGN.md §17).  f32 compute; (8,128)-aligned strips keep loads and
stores lane aligned (``_check_compiled_geometry``).

``interpret`` auto-selects from the backend: compiled on TPU, interpret
mode elsewhere (the kernel body runs with real Pallas semantics on CPU).
``should_stream`` picks the design per (shape, budget); ``pick_bz_block``
/ ``pick_bz_stream``, ``pick_k`` and ``pick_shot_tile`` size the tiling
in closed form from the VMEM budget.  The XLA-path mirrors of the same
tilings are ``ref.py::wave_block_shots_ref`` and
``wave_block_shots_strips_ref`` (bit-exactness oracles).
"""
from __future__ import annotations

import functools
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
        f"(wave_block_shots_stream_pallas), which refuses this fallback.",
        StripFallbackWarning,
        stacklevel=3,
    )
    return nz


def _shift_x(a, d: int, nx: int):
    """x-shift with zero boundary fill along the LAST axis (the (S, win,
    NX) windows of both kernels)."""
    ax = a.ndim - 1
    rolled = jnp.roll(a, d, axis=ax)
    idx = jax.lax.broadcasted_iota(jnp.int32, a.shape, ax)
    if d > 0:
        return jnp.where(idx >= d, rolled, 0.0)
    return jnp.where(idx < nx + d, rolled, 0.0)


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
    regardless of ``s`` (DESIGN.md §17)."""
    bz = min(bz if bz is not None else 128, nz)
    nx = _lanes(nx)
    return 4 * ((2 * s + 2) * nz * nx + 2 * 2 * s * bz * nx + s * k * nx)


def stream_vmem_bytes(nz: int, nx: int, bz: int, k: int, s: int = 1) -> int:
    """VMEM footprint of the STREAMED design: two DMA slots of
    ``2·s + 2`` (win, NX) haloed f32 windows (``2·s`` shot-tiled
    wavefield windows + ONE shared pair of model-field windows), the
    double-buffered output strips, and the double-buffered trace block
    (the shot-batched kernel moves it from one shot tile to the next) —
    O(s·bz·NX), independent of NZ, rows lane-padded (``_lanes``).
    ``s`` is the shot tile, not the batch."""
    win = min(bz + 2 * k * HALO, nz)
    nx = _lanes(nx)
    return 4 * (2 * (2 * s + 2) * win * nx + 2 * 2 * s * bz * nx
                + 2 * s * k * nx)


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
    """k fused timesteps for ALL S shots in ONE pallas_call, the fields
    VMEM-resident (k = src_vals.shape[-1]).

    One grid pass covers the whole batch — the model fields are fetched
    once (not once per shot) and every strip's trapezoid is computed for
    all shots together, so kernel launches and model-field HBM traffic
    are amortized S-fold over one kernel per shot (DESIGN.md §17).
    Strip height defaults to ``pick_bz_block``.  Returns
    (p_k (S, NZ, NX), p_prev_damped_k, traces (S, k, NX)); matches
    ``wave_block_shots_ref`` to stencil-reorder tolerance (the z/x
    accumulation order differs — documented ``allclose``, not bitwise;
    the XLA path carries the bitwise contract)."""
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

    The wavefields stay in HBM as (S, NZ, NX); the grid is (shot tiles,
    strips), tile-major, and each grid step DMAs one tile's
    (tile, win, NX) window pair into one of two VMEM slots.  The model
    fields get their own (2, 2, win, NX) scratch — one (win, NX) window
    per field per slot, DMA'd ONCE per strip and reused by every shot
    of the tile, which is exactly the traffic the shot batch exists to
    amortize (DESIGN.md §17).  The walk is one sequence of steps
    ``i = tile · strips + strip``: step i starts step i+1's fetch into
    the other slot before waiting on its own, so the next window flies
    over this strip's k-step compute (DESIGN.md §15), across a tile
    boundary too.  The trapezoid math is ``_trapezoid_k_steps_shots``,
    shared with the resident kernel."""
    nz = p_hbm.shape[1]
    nx = p_hbm.shape[2]
    ts = fwin_buf.shape[2]           # shots per tile
    nb = nz // bz                    # strips per tile
    tile, strip = pl.program_id(0), pl.program_id(1)
    i = tile * nb + strip            # step of the walk
    n = pl.num_programs(0) * nb
    aligned = _rows_aligned(nz, bz, win, k)

    def win_start(j):
        start = jnp.clip(j * bz - k * HALO, 0, nz - win)
        return pl.multiple_of(start, 8) if aligned else start

    def dma(slot, step):
        start = win_start(step % nb)
        shots = pl.ds((step // nb) * ts, ts)
        copies = [
            pltpu.make_async_copy(
                f.at[shots, pl.ds(start, win), :],
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

    @pl.when(i + 1 < n)              # prefetch the next step's window
    def _prefetch():
        for c in dma((i + 1) % 2, i + 1):
            c.start()

    slot = i % 2
    for c in dma(slot, i):           # wait for our window to land
        c.wait()

    row0 = strip * bz
    start = win_start(strip)
    cur, prevd = _trapezoid_k_steps_shots(
        fwin_buf[slot, 0], fwin_buf[slot, 1],
        mwin_buf[slot, 0], mwin_buf[slot, 1],
        srcv_ref, srcz_ref, srcx_ref, tr_ref,
        start=start, i=strip, win=win, nx=nx, k=k,
        recv=_receiver_window_row(rrow, nz, bz, win, k),
    )
    geometry = dict(nz=nz, bz=bz, win=win, k=k)
    _store_strip(p_out_ref, cur, row0, start, **geometry)
    _store_strip(pp_out_ref, prevd, row0, start, **geometry)


@functools.partial(
    jax.jit,
    static_argnames=("receiver_row", "bz", "interpret", "vmem_budget",
                     "shot_tile"),
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
    shot_tile: int | None = None,
):
    """k fused timesteps for ALL S shots, STREAMED: VMEM holds two
    (tile, win, NX) wavefield window slots plus ONE shared (win, NX)
    model-field slot pair — capacity O(tile·bz·NX), independent of NZ
    and of S.

    ``shot_tile`` (a divisor of S; ``None`` is the whole batch) is how
    many shots share a window: the kernel walks the S / tile tiles in
    its own grid, so the whole batch goes in and comes out whole, with
    no slice or join of the wavefields around the call.  Strip height
    defaults to ``pick_bz_stream(..., s=tile)`` (raises rather than
    fall back to a whole-height resident strip).  Returns
    (p_k, p_prev_damped_k, traces (S, k, NX)), bitwise equal to the
    resident kernel at the same strip geometry: the DMA pipeline is
    pure data movement around the one trapezoid body."""
    ns, nz, nx = p.shape
    k = int(src_vals.shape[-1])
    ts = ns if shot_tile is None else int(shot_tile)
    if interpret is None:
        interpret = default_interpret()
    if bz is None:
        bz = pick_bz_stream(nz, nx, k, vmem_budget=vmem_budget, s=ts)
    budget = vmem_budget if vmem_budget is not None else DEFAULT_VMEM_BUDGET
    win = bz + 2 * k * HALO
    assert ns % ts == 0, (ns, ts)
    assert nz % bz == 0, (nz, bz)
    assert win <= nz, (nz, bz, k)    # no whole-height fallback, ever
    assert stream_vmem_bytes(nz, nx, bz, k, s=ts) <= budget, \
        (nz, nx, bz, k, ts)
    # tile-major: a tile's trace block stays put while its strips run
    grid = (ns // ts, nz // bz)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    strip3 = pl.BlockSpec((ts, bz, nx), lambda t, i: (t, i, 0))
    src = pl.BlockSpec((ts, 1, 1), lambda t, i: (t, 0, 0))
    srcv, srcz, srcx = _norm_src_shots(src_vals, src_z, src_x, ns, p.dtype)
    out_shape = [
        jax.ShapeDtypeStruct((ns, nz, nx), p.dtype),
        jax.ShapeDtypeStruct((ns, nz, nx), p.dtype),
        jax.ShapeDtypeStruct((ns, k, nx), p.dtype),
    ]
    kwargs = {}
    if not interpret:
        _check_compiled_geometry(nz, nx, bz, win, k, stream=True)
        # both axes sequential: each step's DMA was started by the step
        # before it.  The budget counts the buffers; the k-step
        # trapezoid's temporaries come on top (DESIGN.md §15)
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=2 * budget,
        )
    return pl.pallas_call(
        functools.partial(
            _wave_block_shots_stream_kernel, bz=bz, win=win, k=k,
            rrow=int(receiver_row),
        ),
        grid=grid,
        in_specs=[hbm, hbm, hbm, hbm,
                  pl.BlockSpec((ts, 1, k), lambda t, i: (t, 0, 0)),
                  src, src],
        out_specs=[strip3, strip3,
                   pl.BlockSpec((ts, k, nx), lambda t, i: (t, 0, 0))],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((2, 2, ts, win, nx), p.dtype),
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
