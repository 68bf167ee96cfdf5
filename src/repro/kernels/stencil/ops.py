"""Jit'd wrappers for the wave-step / wave-block kernels with portable
fallbacks.

``wave_step`` advances one timestep; ``wave_block`` advances k fused
timesteps (k = src_vals.shape[0]) with source injection, sponge damping
and receiver-row capture in the step epilogue — one kernel launch and
one wavefield HBM round trip per block instead of per step
(DESIGN.md §13).

use_pallas=True runs the Pallas kernels; ``interpret`` auto-selects
from the backend through the ONE shared helper ``default_interpret``
(compiled on TPU; interpret mode elsewhere, where the kernel body still
executes with real Pallas semantics, validating BlockSpec tiling /
trapezoid logic).  use_pallas=False is the pure-jnp path used on
CPU/GPU, and ``use_pallas=None`` picks between the two through
``resolve_use_pallas`` (the kernels on TPU, XLA elsewhere): for
``wave_block`` the XLA path is the jitted k-step fused body
(``wave_block_ref``), BIT-IDENTICAL to k sequential reference steps;
the Pallas block matches to documented `allclose` tolerance (its z/x
stencil accumulation order differs).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.stencil.kernel import (
    autotune_bz,
    autotune_bz_k,
    default_interpret,
    pick_bz,
    pick_bz_block,
    pick_bz_stream,
    pick_k,
    pick_shot_tile,
    resolve_use_pallas,
    resolves_use_pallas,
    should_stream,
    wave_block_pallas,
    wave_block_shots_pallas,
    wave_block_shots_stream_pallas,
    wave_block_stream_pallas,
    wave_step_pallas,
)
from repro.kernels.stencil.ref import (
    wave_block_ref,
    wave_block_shots_ref,
    wave_block_shots_strips_ref,
    wave_block_strips_ref,
    wave_step_ref,
)

__all__ = [
    "wave_step", "wave_step_jit", "wave_step_pallas",
    "wave_block", "wave_block_jit", "wave_block_pallas",
    "wave_block_stream_pallas", "wave_block_strips_ref",
    "wave_block_shots_pallas", "wave_block_shots_stream_pallas",
    "wave_block_shots_ref", "wave_block_shots_strips_ref",
    "autotune_bz", "autotune_bz_k", "default_interpret",
    "pick_bz", "pick_bz_block", "pick_bz_stream", "pick_k",
    "pick_shot_tile", "resolve_use_pallas", "resolves_use_pallas",
    "should_stream",
]


def wave_step(p, p_prev, v2dt2, sponge, *, use_pallas=False,
              bz: int | None = None, interpret: bool | None = None):
    if use_pallas:
        out = wave_step_pallas(
            p, p_prev, v2dt2, sponge, bz=bz, interpret=interpret
        )
        return out[0], out[1]
    return wave_step_ref(p, p_prev, v2dt2, sponge)


wave_step_jit = jax.jit(
    wave_step, static_argnames=("use_pallas", "bz", "interpret")
)


def _wave_block_shots_tiled(
    p, p_prev, v2dt2, sponge, src_vals, src_z, src_x, *,
    receiver_row, use_pallas, bz, interpret, stream, vmem_budget,
    shot_tile,
):
    """Run the shot-batched block kernel over shot tiles of size
    ``shot_tile`` and concatenate — the 3-D dispatch body of
    ``wave_block``.  Per-shot results are independent, so tiling the
    batch is value-preserving (bitwise on the XLA mirror) while keeping
    each pallas_call's VMEM footprint at the tile size, not the full
    batch (DESIGN.md §17)."""
    ns = p.shape[0]
    nz, nx = p.shape[-2], p.shape[-1]
    k = int(src_vals.shape[-1])
    src_z = jnp.asarray(src_z, jnp.int32).reshape(ns)
    src_x = jnp.asarray(src_x, jnp.int32).reshape(ns)
    sv2 = src_vals if getattr(src_vals, "ndim", 1) == 2 else None

    if use_pallas:
        if stream:
            def run(pt, ppt, sv, zt, xt):
                return wave_block_shots_stream_pallas(
                    pt, ppt, v2dt2, sponge, sv, zt, xt,
                    receiver_row=receiver_row, bz=bz, interpret=interpret,
                    vmem_budget=vmem_budget,
                )
        else:
            def run(pt, ppt, sv, zt, xt):
                return wave_block_shots_pallas(
                    pt, ppt, v2dt2, sponge, sv, zt, xt,
                    receiver_row=receiver_row, bz=bz, interpret=interpret,
                )
    elif stream:
        sbz = bz if bz is not None else pick_bz_stream(
            nz, nx, k, vmem_budget=vmem_budget
        )

        def run(pt, ppt, sv, zt, xt):
            return wave_block_shots_strips_ref(
                pt, ppt, v2dt2, sponge, sv, zt, xt,
                receiver_row=receiver_row, bz=sbz,
            )
    else:
        def run(pt, ppt, sv, zt, xt):
            return wave_block_shots_ref(
                pt, ppt, v2dt2, sponge, sv, zt, xt,
                receiver_row=receiver_row,
            )

    if shot_tile >= ns:
        return run(p, p_prev, src_vals, src_z, src_x)
    outs = []
    for lo in range(0, ns, shot_tile):
        hi = min(lo + shot_tile, ns)
        sv = sv2[lo:hi] if sv2 is not None else src_vals
        outs.append(run(p[lo:hi], p_prev[lo:hi], sv,
                        src_z[lo:hi], src_x[lo:hi]))
    return tuple(
        jnp.concatenate([o[i] for o in outs], axis=0) for i in range(3)
    )


def wave_block(p, p_prev, v2dt2, sponge, src_vals, src_z, src_x, *,
               receiver_row: int = 0, use_pallas: bool | None = None,
               bz: int | None = None, interpret: bool | None = None,
               stream: bool | None = None,
               vmem_budget: int | None = None,
               shot_tile: int | None = None):
    """k fused timesteps; returns (p_k, p_prev_damped_k, traces).

    ``p_prev`` follows the engine convention: it is the already
    sponge-damped previous field, and the returned second output is the
    damped p_{k-1} — the (p, p_prev) carry the scan runners thread.

    2-D wavefields dispatch the classic single-shot kernels.  3-D
    ``(S, NZ, NX)`` wavefields dispatch the SHOT-BATCHED engine
    (DESIGN.md §17): the whole batch advances in one kernel per block,
    sharing the model-field reads across shots; ``src_z``/``src_x`` are
    per-shot ``(S,)`` positions and ``src_vals`` may be ``(k,)`` shared
    or ``(S, k)`` per-shot.  ``shot_tile`` bounds how many shots ride
    one pallas_call (VMEM scales with the tile, not the batch);
    ``None`` auto-picks the largest budget-fitting divisor of S via
    ``pick_shot_tile`` on the Pallas path and the whole batch on the
    XLA path, and unaligned explicit tiles run a smaller remainder tile.

    ``stream`` selects the STREAMED tiling for production-scale grids
    (DESIGN.md §15): ``None`` auto-streams when the whole-array
    resident design would blow ``vmem_budget`` (``should_stream``, per
    shot).  On the Pallas path that is ``wave_block_stream_pallas`` /
    ``wave_block_shots_stream_pallas`` (double-buffered window DMA); on
    the pure-XLA path it is the strip-tiled mirror
    (``wave_block_strips_ref`` / ``wave_block_shots_strips_ref``) that
    stays BIT-IDENTICAL to the unstripped reference while bounding the
    per-strip working set — so both backends share one capacity story."""
    k = int(src_vals.shape[-1])
    nz, nx = p.shape[-2], p.shape[-1]
    use_pallas = resolve_use_pallas(use_pallas)
    if stream is None:
        stream = should_stream(nz, nx, k, vmem_budget=vmem_budget)
    if p.ndim == 3:
        ns = p.shape[0]
        if shot_tile is None:
            shot_tile = pick_shot_tile(
                ns, nz, nx, k, bz=bz, stream=stream,
                vmem_budget=vmem_budget,
            ) if use_pallas else ns
        return _wave_block_shots_tiled(
            p, p_prev, v2dt2, sponge, src_vals, src_z, src_x,
            receiver_row=receiver_row, use_pallas=use_pallas, bz=bz,
            interpret=interpret, stream=stream, vmem_budget=vmem_budget,
            shot_tile=int(shot_tile),
        )
    if use_pallas:
        if stream:
            return wave_block_stream_pallas(
                p, p_prev, v2dt2, sponge, src_vals, src_z, src_x,
                receiver_row=receiver_row, bz=bz, interpret=interpret,
                vmem_budget=vmem_budget,
            )
        return wave_block_pallas(
            p, p_prev, v2dt2, sponge, src_vals, src_z, src_x,
            receiver_row=receiver_row, bz=bz, interpret=interpret,
        )
    if stream:
        sbz = bz if bz is not None else pick_bz_stream(
            nz, nx, k, vmem_budget=vmem_budget
        )
        return wave_block_strips_ref(
            p, p_prev, v2dt2, sponge, src_vals, src_z, src_x,
            receiver_row=receiver_row, bz=sbz,
        )
    return wave_block_ref(
        p, p_prev, v2dt2, sponge, src_vals, src_z, src_x,
        receiver_row=receiver_row,
    )


wave_block_jit = jax.jit(
    wave_block,
    static_argnames=("receiver_row", "use_pallas", "bz", "interpret",
                     "stream", "vmem_budget", "shot_tile"),
)
