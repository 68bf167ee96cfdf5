"""Pure-jnp oracle for the FWI wave-equation timestep (kernel ref).

2-D acoustic wave equation, 2nd-order in time, 4th-order in space:

    p_next = 2·p − p_prev + (v·dt)²·∇²p        (+ sponge damping)

4th-order central Laplacian coefficients per axis:
    [-1/12, 4/3, -5/2, 4/3, -1/12] / h²

The sponge multiplies BOTH p_next and p (the damped p becomes the next
step's p_prev), which is why the kernel emits two outputs — one fused
pass over the fields (the memory-bound hot loop of the paper's app).
Boundary cells use zero halo (free-surface-ish); the sponge absorbs
before reflections matter.

Two Laplacian formulations live here:

* ``laplacian`` — ONE zero-pad then nine static slices.  This is the
  production form: XLA fuses the slice-adds into a single pass, so the
  only extra materialization is the padded copy.
* ``laplacian_roll`` — the original roll-then-mask form (8 rolls + 8
  masked sets per step, each a full-array copy on CPU), kept as an
  independent oracle.

Both accumulate terms in the SAME order (center, then the d=1 ring
z/x, then the d=2 ring), so they are bit-identical in f32 — the fused
scan engine built on the fast form reproduces seed results exactly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

C0 = -5.0 / 2.0
C1 = 4.0 / 3.0
C2 = -1.0 / 12.0

_PAD = 2     # stencil reach per axis


def _shift(p: jnp.ndarray, dz: int, dx: int) -> jnp.ndarray:
    """Shift with zero fill (zero halo at physical boundary)."""
    out = p
    if dz:
        out = jnp.roll(out, dz, axis=-2)
        if dz > 0:
            out = out.at[..., :dz, :].set(0.0)
        else:
            out = out.at[..., dz:, :].set(0.0)
    if dx:
        out = jnp.roll(out, dx, axis=-1)
        if dx > 0:
            out = out.at[..., :, :dx].set(0.0)
        else:
            out = out.at[..., :, dx:].set(0.0)
    return out


def laplacian_roll(p: jnp.ndarray, inv_h2: float = 1.0) -> jnp.ndarray:
    """Seed formulation: roll + masked set per shifted term."""
    lap = 2.0 * C0 * p
    for d in (1, 2):
        c = C1 if d == 1 else C2
        lap = lap + c * (
            _shift(p, d, 0) + _shift(p, -d, 0)
            + _shift(p, 0, d) + _shift(p, 0, -d)
        )
    return lap * inv_h2


def laplacian(p: jnp.ndarray, inv_h2: float = 1.0) -> jnp.ndarray:
    """Pad-and-slice formulation; bit-identical to ``laplacian_roll``."""
    nz, nx = p.shape[-2], p.shape[-1]
    widths = [(0, 0)] * (p.ndim - 2) + [(_PAD, _PAD), (_PAD, _PAD)]
    padded = jnp.pad(p, widths)

    def sh(dz: int, dx: int) -> jnp.ndarray:
        # equals _shift(p, dz, dx): padded window offset by (-dz, -dx)
        return padded[..., _PAD - dz: _PAD - dz + nz,
                      _PAD - dx: _PAD - dx + nx]

    lap = 2.0 * C0 * p
    for d in (1, 2):
        c = C1 if d == 1 else C2
        lap = lap + c * (sh(d, 0) + sh(-d, 0) + sh(0, d) + sh(0, -d))
    return lap * inv_h2


def wave_step_ref(
    p: jnp.ndarray,        # (..., NZ, NX) current pressure
    p_prev: jnp.ndarray,   # (..., NZ, NX)
    v2dt2: jnp.ndarray,    # (NZ, NX) or broadcastable: (v·dt)²/h²
    sponge: jnp.ndarray,   # (NZ, NX) damping taper in [0, 1]
):
    """One timestep.  Returns (p_next, p_damped) both sponge-damped."""
    lap = laplacian(p)
    p_next = (2.0 * p - p_prev + v2dt2 * lap) * sponge
    return p_next, p * sponge


def laplacian_of_padded(padded: jnp.ndarray, nz: int, nx: int) -> jnp.ndarray:
    """``laplacian`` reading an ALREADY-padded field (..., NZ+4, NX+4).

    Same nine slices, same accumulation order — bit-identical to
    ``laplacian(padded[..., 2:-2, 2:-2])`` — but without re-materializing
    the padded copy every step.  The k-step fused block keeps the field
    padded across inner steps, so the per-step ``jnp.pad`` of the
    production form disappears (DESIGN.md §13).
    """

    def sh(dz: int, dx: int) -> jnp.ndarray:
        return padded[..., _PAD - dz: _PAD - dz + nz,
                      _PAD - dx: _PAD - dx + nx]

    lap = 2.0 * C0 * sh(0, 0)
    for d in (1, 2):
        c = C1 if d == 1 else C2
        lap = lap + c * (sh(d, 0) + sh(-d, 0) + sh(0, d) + sh(0, -d))
    return lap


def wave_block_ref(
    p: jnp.ndarray,        # (NZ, NX) current pressure
    p_prev: jnp.ndarray,   # (NZ, NX) previous, already sponge-damped
    v2dt2: jnp.ndarray,    # (NZ, NX)
    sponge: jnp.ndarray,   # (NZ, NX)
    src_vals: jnp.ndarray,  # (k,) source amplitude per inner step
    src_z,                 # scalar int source row
    src_x,                 # scalar int source column
    *,
    receiver_row: int = 0,
):
    """k fused timesteps with in-block source injection + receiver rows.

    The single-shot pure-XLA block (k is static,
    read off ``src_vals.shape``).  Two fusions vs the step-at-a-time
    form (DESIGN.md §13):

    * the field stays PADDED across inner steps (one pad on entry, one
      slice on exit) instead of one ``jnp.pad`` materialization per step;
    * the damped previous field is folded into the next step's leapfrog
      expression (``cur * sponge`` fuses into the elementwise update)
      instead of being materialized as a second full-array output every
      step — only the final block boundary writes it.

    Both are pure re-schedulings of the identical ops in identical
    order: the k-step result is BIT-IDENTICAL to k sequential
    ``wave_step_ref`` + injection steps (the contract the equivalence
    tests pin).  Returns (p_k, p_prev_damped_k, traces (k, NX)).
    """
    k = src_vals.shape[0]
    nz, nx = p.shape[-2], p.shape[-1]
    ppad = jnp.pad(p, ((_PAD, _PAD), (_PAD, _PAD)))
    prevd = p_prev
    traces = []
    for j in range(k):
        cur = ppad[_PAD: _PAD + nz, _PAD: _PAD + nx]
        lap = laplacian_of_padded(ppad, nz, nx)
        pn = (2.0 * cur - prevd + v2dt2 * lap) * sponge
        pn = pn.at[src_z, src_x].add(src_vals[j])
        traces.append(
            jax.lax.dynamic_slice_in_dim(pn, receiver_row, 1, axis=0)[0]
        )
        prevd = cur * sponge
        ppad = jax.lax.dynamic_update_slice(ppad, pn, (_PAD, _PAD))
    return (ppad[_PAD: _PAD + nz, _PAD: _PAD + nx], prevd,
            jnp.stack(traces))


def wave_block_shots_ref(
    p: jnp.ndarray,        # (S, NZ, NX) shot batch, current pressure
    p_prev: jnp.ndarray,   # (S, NZ, NX) previous, already sponge-damped
    v2dt2: jnp.ndarray,    # (NZ, NX) shared model field
    sponge: jnp.ndarray,   # (NZ, NX) shared model field
    src_vals: jnp.ndarray,  # (k,) shared or (S, k) per-shot amplitudes
    src_z,                 # (S,) int per-shot source rows
    src_x,                 # (S,) int per-shot source columns
    *,
    receiver_row: int = 0,
):
    """Shot-batched ``wave_block_ref`` — the XLA mirror of the batched
    Pallas kernel, BIT-IDENTICAL to ``vmap``-of-``wave_block_ref``.

    The whole shot batch advances k steps in one padded-field sweep:
    the Laplacian slices, leapfrog and sponge are elementwise over the
    leading shot axis (slicing commutes with the batch, so every shot's
    value stream is the op-for-op vmap lowering), and the per-shot
    source injection scatters to ``(shot, z_s, x_s)`` — one element per
    batch row, so the adds are order-independent and bitwise equal to
    the per-shot ``at[z, x].add``.  This is the dispatch target
    ``ops.wave_block`` uses on the XLA path, keeping the
    engine's bitwise contract intact while the model fields are shared
    (DESIGN.md §17).  Returns (p_k, p_prev_damped_k, traces (S, k, NX)).
    """
    ns, nz, nx = p.shape
    k = src_vals.shape[-1]
    sv = jnp.asarray(src_vals, p.dtype)
    if sv.ndim == 1:
        sv = jnp.broadcast_to(sv, (ns, k))
    zi = jnp.broadcast_to(jnp.asarray(src_z, jnp.int32), (ns,))
    xi = jnp.broadcast_to(jnp.asarray(src_x, jnp.int32), (ns,))
    sidx = jnp.arange(ns)
    ppad = jnp.pad(p, ((0, 0), (_PAD, _PAD), (_PAD, _PAD)))
    prevd = p_prev
    traces = []
    for j in range(k):
        cur = ppad[:, _PAD: _PAD + nz, _PAD: _PAD + nx]
        lap = laplacian_of_padded(ppad, nz, nx)
        pn = (2.0 * cur - prevd + v2dt2 * lap) * sponge
        pn = pn.at[sidx, zi, xi].add(sv[:, j])
        traces.append(
            jax.lax.dynamic_slice_in_dim(pn, receiver_row, 1, axis=1)[:, 0]
        )
        prevd = cur * sponge
        ppad = jax.lax.dynamic_update_slice(ppad, pn, (0, _PAD, _PAD))
    return (ppad[:, _PAD: _PAD + nz, _PAD: _PAD + nx], prevd,
            jnp.stack(traces, axis=1))


def wave_block_shots_strips_ref(
    p: jnp.ndarray,        # (S, NZ, NX) shot batch, current pressure
    p_prev: jnp.ndarray,   # (S, NZ, NX) previous, already sponge-damped
    v2dt2: jnp.ndarray,    # (NZ, NX) shared model field
    sponge: jnp.ndarray,   # (NZ, NX) shared model field
    src_vals: jnp.ndarray,  # (k,) shared or (S, k) per-shot amplitudes
    src_z,                 # (S,) int per-shot source rows
    src_x,                 # (S,) int per-shot source columns
    *,
    receiver_row: int = 0,
    bz: int,
):
    """Shot-batched ``wave_block_strips_ref`` — the strip-tiled XLA
    mirror of the batched STREAMED kernel, BIT-IDENTICAL to both
    ``wave_block_shots_ref`` and ``vmap``-of-``wave_block_strips_ref``.

    Windows carry a leading shot axis — (n_strips, S, win, NX) — while
    the model-field windows stay (n_strips, win, NX) and broadcast
    across shots, mirroring the streamed kernel's single model-field
    DMA slot.  Per-(strip, shot) source injection scatters one element
    per pair (order-independent adds), masked to windows that contain
    the shot's source row, exactly as the single-shot strips mirror
    masks its in-window injection (DESIGN.md §17)."""
    ns, nz, nx = p.shape
    k = src_vals.shape[-1]
    assert nz % bz == 0, (nz, bz)
    win = min(bz + 2 * k * _PAD, nz)
    n = nz // bz
    starts = [min(max(i * bz - k * _PAD, 0), nz - win) for i in range(n)]
    offs = [i * bz - starts[i] for i in range(n)]    # strip offset in window
    stidx = jnp.asarray(starts, jnp.int32)
    oidx = jnp.asarray(offs, jnp.int32)
    sv = jnp.asarray(src_vals, p.dtype)
    if sv.ndim == 1:
        sv = jnp.broadcast_to(sv, (ns, k))
    src_zv = jnp.broadcast_to(jnp.asarray(src_z, jnp.int32), (ns,))
    src_xv = jnp.broadcast_to(jnp.asarray(src_x, jnp.int32), (ns,))

    def windows(a):                   # (S, NZ, NX) -> (n, S, win, NX)
        return jax.vmap(
            lambda st: jax.lax.dynamic_slice_in_dim(a, st, win, axis=-2)
        )(stidx)

    prevd = windows(p_prev)
    vw = windows(v2dt2)               # (n, win, NX), shared across shots
    sw = windows(sponge)
    ppad = jnp.pad(windows(p), ((0, 0), (0, 0), (_PAD, _PAD), (_PAD, _PAD)))
    ow = receiver_row // bz                          # receiver-owning strip
    zi = src_zv[None, :] - stidx[:, None]            # (n, S) in-window rows
    inb = (zi >= 0) & (zi < win)
    zidx = jnp.clip(zi, 0, win - 1)
    ii = jnp.broadcast_to(jnp.arange(n)[:, None], (n, ns))
    ss = jnp.broadcast_to(jnp.arange(ns)[None, :], (n, ns))
    xx = jnp.broadcast_to(src_xv[None, :], (n, ns))
    traces = []
    for j in range(k):
        cur = ppad[:, :, _PAD: _PAD + win, _PAD: _PAD + nx]
        lap = laplacian_of_padded(ppad, win, nx)
        pn = (2.0 * cur - prevd + vw[:, None] * lap) * sw[:, None]
        # every window containing a shot's source row injects for that
        # shot; out-of-window pairs add a masked zero on a clipped row
        amt = jnp.where(inb, sv[None, :, j], jnp.zeros((), pn.dtype))
        pn = pn.at[ii, ss, zidx, xx].add(amt)
        traces.append(pn[ow, :, receiver_row - starts[ow], :])
        prevd = cur * sw[:, None]
        ppad = jax.lax.dynamic_update_slice(ppad, pn, (0, 0, _PAD, _PAD))

    def owned(w, off):                # (S, win, nx) -> (S, bz, nx)
        return jax.lax.dynamic_slice_in_dim(w, off, bz, axis=-2)

    p_out = jnp.moveaxis(jax.vmap(owned)(
        ppad[:, :, _PAD: _PAD + win, _PAD: _PAD + nx], oidx
    ), 0, 1).reshape(ns, nz, nx)
    pp_out = jnp.moveaxis(jax.vmap(owned)(prevd, oidx), 0, 1).reshape(
        ns, nz, nx)
    return p_out, pp_out, jnp.stack(traces, axis=1)


def wave_block_strips_ref(
    p: jnp.ndarray,        # (NZ, NX) current pressure
    p_prev: jnp.ndarray,   # (NZ, NX) previous, already sponge-damped
    v2dt2: jnp.ndarray,    # (NZ, NX)
    sponge: jnp.ndarray,   # (NZ, NX)
    src_vals: jnp.ndarray,  # (k,) source amplitude per inner step
    src_z,                 # scalar int source row
    src_x,                 # scalar int source column
    *,
    receiver_row: int = 0,
    bz: int,
):
    """``wave_block_ref`` re-tiled over z-strips — the XLA mirror of the
    STREAMED kernel's schedule, BIT-IDENTICAL to ``wave_block_ref``.

    Each of the nz/bz strips computes its k steps on a
    ``win = bz + 2·k·HALO`` haloed window (start clamped into the field,
    exactly the kernel's trapezoid), vmapped over strips so the working
    set per strip is O(win·NX) regardless of NZ.  Zero-extending a
    window seeds wrong values at interior window edges whose influence
    creeps inward HALO rows per step; the clamp keeps every owned strip
    ≥ k·HALO rows from any interior edge, so after k steps the owned
    rows are untouched by the creep — and since slicing commutes with
    elementwise ops and the Laplacian accumulates in the same order as
    ``laplacian_of_padded`` on the full field, the owned rows are
    bitwise equal to the unstripped reference.  This is the streamed
    path's bit-exactness oracle (DESIGN.md §15): the Pallas streamed
    kernel matches to its documented stencil-reorder `allclose`, this
    mirror matches ``wave_block_ref`` exactly."""
    k = src_vals.shape[0]
    nz, nx = p.shape[-2], p.shape[-1]
    assert nz % bz == 0, (nz, bz)
    win = min(bz + 2 * k * _PAD, nz)
    n = nz // bz
    starts = [min(max(i * bz - k * _PAD, 0), nz - win) for i in range(n)]
    offs = [i * bz - starts[i] for i in range(n)]    # strip offset in window
    sidx = jnp.asarray(starts, jnp.int32)
    oidx = jnp.asarray(offs, jnp.int32)

    def windows(a):
        return jax.vmap(
            lambda s: jax.lax.dynamic_slice_in_dim(a, s, win, axis=0)
        )(sidx)

    prevd = windows(p_prev)
    vw = windows(v2dt2)
    sw = windows(sponge)
    ppad = jnp.pad(windows(p), ((0, 0), (_PAD, _PAD), (_PAD, _PAD)))
    ow = receiver_row // bz                          # receiver-owning strip
    zi = jnp.asarray(src_z, jnp.int32) - sidx        # (n,) in-window src row
    inb = (zi >= 0) & (zi < win)
    zidx = jnp.clip(zi, 0, win - 1)
    traces = []
    for j in range(k):
        cur = ppad[:, _PAD: _PAD + win, _PAD: _PAD + nx]
        lap = laplacian_of_padded(ppad, win, nx)
        pn = (2.0 * cur - prevd + vw * lap) * sw
        # every window containing the source row injects (neighbors need
        # it too — its influence creeps into their owned strip); masked
        # zero-adds land only on dirty halo rows, never owned ones
        amt = jnp.where(inb, src_vals[j], jnp.zeros((), pn.dtype))
        pn = jax.vmap(lambda f, z, a: f.at[z, src_x].add(a))(pn, zidx, amt)
        traces.append(pn[ow, receiver_row - starts[ow], :])
        prevd = cur * sw
        ppad = jax.lax.dynamic_update_slice(ppad, pn, (0, _PAD, _PAD))

    def owned(w, off):                               # (win, nx) -> (bz, nx)
        return jax.lax.dynamic_slice_in_dim(w, off, bz, axis=0)

    p_out = jax.vmap(owned)(
        ppad[:, _PAD: _PAD + win, _PAD: _PAD + nx], oidx
    ).reshape(nz, nx)
    pp_out = jax.vmap(owned)(prevd, oidx).reshape(nz, nx)
    return p_out, pp_out, jnp.stack(traces)
