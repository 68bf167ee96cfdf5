"""Dispatch for the wave-block kernels, with a portable fallback.

``wave_block`` advances a shot batch — (S, NZ, NX) wavefields over
shared (NZ, NX) model fields — k fused timesteps (k =
src_vals.shape[-1]) with source injection, sponge damping and
receiver-row capture in the step epilogue: one kernel launch and one
wavefield HBM round trip per block instead of per step (DESIGN.md §13).
One shot is the batch S=1.

use_pallas=True runs the shot-batched Pallas kernels; ``interpret``
auto-selects from the backend through the ONE shared helper
``default_interpret`` (compiled on TPU; interpret mode elsewhere, where
the kernel body still executes with real Pallas semantics, validating
BlockSpec tiling / trapezoid logic).  use_pallas=False is the pure-jnp
path used on CPU/GPU, and ``use_pallas=None`` picks between the two
through ``resolve_use_pallas`` (the kernels on TPU, XLA elsewhere).
The XLA path (``wave_block_shots_ref`` / ``wave_block_shots_strips_ref``)
is BIT-IDENTICAL to k sequential reference steps; the Pallas kernels
match to documented ``allclose`` tolerance (their z/x stencil
accumulation order differs).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.stencil.kernel import (
    default_interpret,
    pick_bz_block,
    pick_bz_stream,
    pick_k,
    pick_shot_tile,
    resolve_use_pallas,
    resolves_use_pallas,
    should_stream,
    wave_block_shots_pallas,
    wave_block_shots_stream_pallas,
)
from repro.kernels.stencil.ref import (
    wave_block_shots_ref,
    wave_block_shots_strips_ref,
)

__all__ = [
    "wave_block", "wave_block_shots_pallas",
    "wave_block_shots_stream_pallas", "wave_block_shots_ref",
    "wave_block_shots_strips_ref", "default_interpret",
    "pick_bz_block", "pick_bz_stream", "pick_k", "pick_shot_tile",
    "resolve_use_pallas", "resolves_use_pallas", "should_stream",
]


def _wave_block_shots_tiled(
    p, p_prev, v2dt2, sponge, src_vals, src_z, src_x, *,
    receiver_row, use_pallas, bz, interpret, stream, vmem_budget,
    shot_tile,
):
    """Run the shot-batched block kernel over shot tiles of size
    ``shot_tile`` — the dispatch body of ``wave_block``.  Per-shot
    results are independent, so tiling the batch is value-preserving
    (bitwise on the XLA mirror) while keeping each pallas_call's VMEM
    footprint at the tile size, not the full batch (DESIGN.md §17).

    The streamed Pallas kernel walks the tiles in its own grid, so a
    tile that divides the batch is one call on the whole batch.  The
    resident and XLA paths, and a ragged explicit tile, slice the batch
    into tiles and concatenate what they return."""
    ns = p.shape[0]
    nz, nx = p.shape[-2], p.shape[-1]
    k = int(src_vals.shape[-1])
    src_z = jnp.asarray(src_z, jnp.int32).reshape(ns)
    src_x = jnp.asarray(src_x, jnp.int32).reshape(ns)
    sv2 = src_vals if getattr(src_vals, "ndim", 1) == 2 else None

    if use_pallas and stream and ns % shot_tile == 0:
        return wave_block_shots_stream_pallas(
            p, p_prev, v2dt2, sponge, src_vals, src_z, src_x,
            receiver_row=receiver_row, bz=bz, interpret=interpret,
            vmem_budget=vmem_budget, shot_tile=shot_tile,
        )
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
        with jax.named_scope("stencil.tile_split"):
            sv = sv2[lo:hi] if sv2 is not None else src_vals
            tile = (p[lo:hi], p_prev[lo:hi], sv, src_z[lo:hi], src_x[lo:hi])
        outs.append(run(*tile))
    with jax.named_scope("stencil.tile_concat"):
        return tuple(
            jnp.concatenate([o[i] for o in outs], axis=0) for i in range(3)
        )


def wave_block(p, p_prev, v2dt2, sponge, src_vals, src_z, src_x, *,
               receiver_row: int = 0, use_pallas: bool | None = None,
               bz: int | None = None, interpret: bool | None = None,
               stream: bool | None = None,
               vmem_budget: int | None = None,
               shot_tile: int | None = None):
    """k fused timesteps of a shot batch; returns (p_k, p_prev_damped_k,
    traces (S, k, NX)).

    ``p``/``p_prev`` are (S, NZ, NX); any other rank raises
    ``ValueError``.  ``p_prev`` follows the engine convention: it is the
    already sponge-damped previous field, and the returned second
    output is the damped p_{k-1} — the (p, p_prev) carry the scan
    runners thread.

    The whole batch advances in one kernel per block (DESIGN.md §17),
    sharing the model-field reads across shots; ``src_z``/``src_x`` are
    per-shot ``(S,)`` positions and ``src_vals`` may be ``(k,)`` shared
    or ``(S, k)`` per-shot.  ``shot_tile`` bounds how many shots are
    computed together (VMEM scales with the tile, not the batch);
    ``None`` auto-picks the largest budget-fitting divisor of S via
    ``pick_shot_tile`` on the Pallas path and the whole batch on the
    XLA path.  The streamed Pallas kernel walks the tiles in its own
    grid, on the whole batch; the resident and XLA paths, and ragged
    explicit tiles (which run a smaller remainder tile), slice the
    batch into tiles and join the results.

    ``stream`` selects the STREAMED tiling for production-scale grids
    (DESIGN.md §15): ``None`` auto-streams when the whole-array
    resident design would blow ``vmem_budget`` (``should_stream``, per
    shot).  On the Pallas path that is ``wave_block_shots_stream_pallas``
    (double-buffered window DMA); on the pure-XLA path it is the
    strip-tiled mirror ``wave_block_shots_strips_ref``, which stays
    BIT-IDENTICAL to the unstripped reference while bounding the
    per-strip working set — so both backends share one capacity story."""
    if p.ndim != 3:
        raise ValueError(
            f"wave_block takes (S, NZ, NX) shot batches, got shape "
            f"{p.shape}; pass one shot as p[None]")
    k = int(src_vals.shape[-1])
    ns, nz, nx = p.shape
    use_pallas = resolve_use_pallas(use_pallas)
    if stream is None:
        stream = should_stream(nz, nx, k, vmem_budget=vmem_budget)
    if shot_tile is None:
        shot_tile = pick_shot_tile(
            ns, nz, nx, k, bz=bz, stream=stream, vmem_budget=vmem_budget,
        ) if use_pallas else ns
    return _wave_block_shots_tiled(
        p, p_prev, v2dt2, sponge, src_vals, src_z, src_x,
        receiver_row=receiver_row, use_pallas=use_pallas, bz=bz,
        interpret=interpret, stream=stream, vmem_budget=vmem_budget,
        shot_tile=int(shot_tile),
    )
