"""Striped domain decomposition + overlapped, temporally-blocked halo
exchange (paper Fig. 2, communication-avoiding + communication-hiding).

The x-axis (width) is cut into contiguous column stripes, one per device
on a 1-D ("stripe",) mesh; the height is fixed — exactly the paper's
simplification.  The γ-split maps stripes to environments: with the
right γ·(NX/stripes) columns assigned to burst-pod devices, only ONE
stripe seam crosses the slow link (greedy striped placement, §3.3).

Communication avoidance (the paper's "total message size is only 21 KB"
measurement is about per-step seam LATENCY, which dominates over the
slow cluster↔cloud link): instead of a 2-column (HALO) exchange every
timestep, each stripe exchanges a k·HALO-wide halo ONCE and then runs k
timesteps with ZERO communication.  Incorrect values creep inward from
a window edge at HALO cells per step, so after k steps exactly the
owned region is clean — standard overlapping ("ghost-zone") temporal
blocking.  For k > 1 the previous-field edges ride in the SAME message
(stacked), so ppermute invocations per timestep drop k× (2 per block vs
2 per step) while amortized bytes stay flat.

Communication HIDING comes in three schedules (DESIGN.md §13, §15):

* ``"overlap"`` — within one block: the packed exchange is issued
  FIRST; the INTERIOR of the stripe — every column ≥ k·HALO from a
  seam, which by construction never reads the halo within one k-step
  block — is computed as one fused ``wave_block`` while the ppermute
  is in flight; two narrow (3·k·HALO-column) BOUNDARY windows that do
  consume the received halos are computed after and stitched in.
  Per-block cost drops from ``compute + seam`` to
  ``max(interior, seam) + boundary``.
* ``"pipeline"`` — ACROSS scan blocks: the received halos ride in the
  scan carry, each block computes its boundary windows first from the
  CARRIED halos, issues the NEXT block's exchange from their fresh
  edge columns, then computes interior + stitch — so a whole block of
  compute covers each exchange instead of only the interior window
  (one eager prologue exchange; one wasted epilogue exchange).  The
  per-block op graph is the overlap schedule's, reordered: pinned
  BITWISE equal.
* ``"fused"`` — comm-avoiding single window, exchange on the critical
  path, least redundant compute (2·k·HALO columns vs 6·k·HALO for the
  split schedules).

The splits only pay where collectives are async, so ``pick_schedule``
auto-selects per backend (TPU: "pipeline"; synchronous hosts:
"fused"); ``pick_overlap`` is the legacy boolean view.
``halo_exchange_plan`` exports the seam-traffic AND overlap
bookkeeping (``overlap_fraction``) that ``OverheadModel
.with_overlapped_seam``, the ``measure_seam_latency`` probe and the
overhead benches consume.

Physical domain edges need no special-casing: every window is
zero-extended in x, which at a physical edge IS the reference's
zero-halo convention, and at a seam marks the redundant zone that the
trapezoidal shrink discards.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.fwi.solver import FWIConfig, ricker, sponge_taper, velocity_model
from repro.kernels.stencil.ops import resolves_use_pallas, wave_block
from repro.launch.mesh import make_mesh

HALO = 2


def stripe_mesh(n_devices: int | None = None) -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    return make_mesh((n,), ("stripe",), devices=devs[:n])


def pick_overlap(backend: str | None = None) -> bool:
    """Schedule selection for the sharded block body (DESIGN.md §13).

    The interior/boundary overlap split only pays where collectives are
    ASYNC (TPU: collective-permute-start/done hide behind the interior
    fusion); on hosts whose ppermute is synchronous the split is pure
    overhead — 6·k·HALO redundant columns instead of 2·k·HALO — so the
    comm-avoiding single-window schedule wins.  Same auto-selection
    spirit as the kernel's ``default_interpret``/``pick_bz``.

    Kept as the boolean (PR 3) view of the choice; the full three-way
    schedule selection lives in ``pick_schedule`` (DESIGN.md §15)."""
    return (backend or jax.default_backend()) == "tpu"


def pick_schedule(backend: str | None = None) -> str:
    """Three-way schedule auto-selection for the sharded scan runner.

    * ``"pipeline"`` — double-buffered halo exchange ACROSS scan blocks:
      block b+1's packed ppermute is issued before block b's interior
      compute and seam stitch, so the exchange hides behind a whole
      block of work instead of only the same block's interior window.
      Needs async collectives — selected on TPU.
    * ``"overlap"``  — PR 3's within-block split (exchange first,
      interior while it flies, boundary windows after).
    * ``"fused"``    — comm-avoiding single window, exchange on the
      critical path; least redundant compute, the right choice where
      collectives are synchronous anyway (CPU hosts).

    All three produce BIT-IDENTICAL results on the XLA path — the
    invariance tests pin it — so this is purely a performance choice;
    ``measure_seam_latency`` (fwi/calibrate.py) audits it."""
    return "pipeline" if (backend or jax.default_backend()) == "tpu" \
        else "fused"


def _as_schedule(overlap) -> str:
    """Normalize the legacy bool knob: True -> "overlap", False ->
    "fused"; strings pass through; None -> ``pick_schedule()``."""
    if overlap is None:
        return pick_schedule()
    if isinstance(overlap, str):
        if overlap not in ("fused", "overlap", "pipeline"):
            raise ValueError(f"unknown halo schedule: {overlap!r}")
        return overlap
    return "overlap" if overlap else "fused"


def _exchange_halo(edges_r: jnp.ndarray, edges_l: jnp.ndarray,
                   axis_name: str):
    """One packed bidirectional exchange.  ``edges_r``/``edges_l`` are
    my right/left edge payloads (..., NZ, pad); returns what I receive
    from my left/right neighbors, zeroed at the physical domain edge.
    Exactly TWO ppermutes regardless of how many fields are packed in."""
    idx = jax.lax.axis_index(axis_name)
    n = jax.lax.axis_size(axis_name)
    from_left = jax.lax.ppermute(
        edges_r, axis_name, [(i, (i + 1) % n) for i in range(n)]
    )
    from_right = jax.lax.ppermute(
        edges_l, axis_name, [(i, (i - 1) % n) for i in range(n)]
    )
    zero = jnp.zeros_like(from_left)
    left_halo = jnp.where(idx == 0, zero, from_left)
    right_halo = jnp.where(idx == n - 1, zero, from_right)
    return left_halo, right_halo


def _overlapped_field(arr: np.ndarray, n: int, pad: int) -> jnp.ndarray:
    """(NZ, NX) -> (n, NZ, NXl + 2·pad) per-stripe windows with real
    neighbor values in the overlap and zeros outside the domain."""
    nz, nx = arr.shape
    nxl = nx // n
    a = np.pad(np.asarray(arr), ((0, 0), (pad, pad)))
    return jnp.asarray(np.stack(
        [a[:, i * nxl: i * nxl + nxl + 2 * pad] for i in range(n)]
    ), jnp.float32)


def effective_block(cfg: FWIConfig, n_stripes: int, k: int) -> int:
    """Clamp k so the overlap windows fit inside one stripe: the
    interior/boundary split needs the two 2·k·HALO-column boundary
    source regions to be disjoint, i.e. 2·k·HALO ≤ NX/stripes."""
    nxl = cfg.nx // n_stripes
    return max(1, min(k, nxl // (2 * HALO)))


@functools.lru_cache(maxsize=32)
def _sharded_block_parts(cfg: FWIConfig, mesh: Mesh, k: int,
                         use_pallas: bool, bz: int | None = None,
                         schedule: str = "overlap"):
    """(sms, v2e_all, spe_all, place, k): the UNJITTED shard_map'd
    k-step fused block bodies plus their closure fields — callers jit at
    their own boundary (wrapping the body in its own jit inside a
    lax.scan defeats XLA's loop fusion; see solver.py).  ``sms`` is a
    dict: ``{"block"}`` for the "fused"/"overlap" schedules,
    ``{"prologue", "pipeline"}`` for "pipeline".

    schedule="overlap" realizes the within-block comm/compute-overlap
    schedule (DESIGN.md §13): packed halo ppermute issued first; the
    stripe INTERIOR advanced k fused steps (independent of the
    exchange, overlappable with it); the two 3·k·HALO boundary windows
    — batched into ONE ``wave_block`` call — consume the received halos
    and patch the k·HALO seam-adjacent column strips.
    schedule="fused" is the comm-AVOIDING schedule only: one fused
    window over the whole extended stripe, exchange on the critical
    path (less redundant compute — 2·k·HALO vs 6·k·HALO extra columns —
    for hosts whose collectives are synchronous anyway).
    schedule="pipeline" double-buffers the exchange ACROSS scan blocks
    (DESIGN.md §15): the halos arrive in the scan CARRY, the boundary
    windows run first (their valid columns are the stripe's fresh
    edges), block b+1's packed ppermute is issued from those fresh
    edges BEFORE block b's interior compute and seam stitch, and the
    interior fusion plus stitch fly under it.

    On the XLA path the overlap and pipeline schedules are pinned
    bitwise-identical to the reference (the pipeline computes the same
    per-block graph as overlap — only the exchange's position in the
    schedule moves); the single-window schedule computes the identical
    op sequence but its different fusion shapes may flush denormal
    wavefront tails differently — equal up to sub-normal (< 1.2e-38)
    noise.
    """
    n = mesh.shape["stripe"]
    assert cfg.nx % n == 0, (cfg.nx, n)
    nxl = cfg.nx // n
    k = effective_block(cfg, n, k)
    pad = k * HALO
    v = velocity_model(cfg)
    v2dt2 = (v * cfg.dt / cfg.dx) ** 2
    sponge = sponge_taper(cfg)
    v2e_all = _overlapped_field(np.asarray(v2dt2), n, pad)
    spe_all = _overlapped_field(np.asarray(sponge), n, pad)
    wavelet = ricker(cfg)
    pos = cfg.shot_positions()
    src_z = jnp.asarray(pos[:, 0])
    src_x = jnp.asarray(pos[:, 1])
    sh = NamedSharding(mesh, P(None, None, "stripe"))

    def exchange_edges(p_r, p_l, pp_r, pp_l):
        # ONE packed exchange for the whole k-step block; for k > 1 the
        # p_prev edges ride in the same message (leading stacked axis)
        if k > 1:
            left, right = _exchange_halo(
                jnp.stack([p_r, pp_r]), jnp.stack([p_l, pp_l]), "stripe"
            )
            return left[0], right[0], left[1], right[1]
        lh_p, rh_p = _exchange_halo(p_r, p_l, "stripe")
        # k=1 never reads the p_prev halo (halo outputs are discarded
        # after one step) — zero-extend
        z = jnp.zeros_like(pp_l)
        return lh_p, rh_p, z, z

    def make_srcv(t0):
        return wavelet[
            jnp.clip(t0 + jnp.arange(k), 0, cfg.timesteps - 1)
        ] * (cfg.dt ** 2)

    # --- k fused steps on a window via wave_block -------------------
    def window(px, ppx, vw, sw, wx0, x0, srcv):
        # wx0: local column of window column 0 (traced).  Sources
        # inject into EVERY window covering their column, so redundant
        # zones track true neighbor physics; each window's valid region
        # is stitched disjointly below.  The whole shot batch advances
        # in ONE shot-batched wave_block (3-D dispatch, DESIGN.md §17)
        # with per-shot (S, k) amplitudes masked by window coverage —
        # bitwise-equal to the old vmap-of-per-shot form on the XLA
        # path (wave_block_shots_ref's pinned contract).
        w = px.shape[-1]
        xloc = src_x - x0 - wx0                  # (S,) per-shot column
        covered = (xloc >= 0) & (xloc < w)
        sv = jnp.where(covered[:, None], srcv[None, :], 0.0)
        xc = jnp.clip(xloc, 0, w - 1)
        return wave_block(
            px, ppx, vw, sw, sv, src_z, xc,
            receiver_row=cfg.receiver_depth,
            use_pallas=use_pallas, bz=bz,
        )

    def interior(p, p_prev, v2e, spe, x0, srcv):
        # valid after k steps: columns [pad, nxl-pad) — everything the
        # seams cannot influence within one block
        return window(
            p, p_prev, v2e[:, pad: pad + nxl], spe[:, pad: pad + nxl],
            0, x0, srcv,
        )

    def boundary(p, p_prev, lh_p, rh_p, lh_pp, rh_pp, v2e, spe, x0, srcv):
        # two BOUNDARY windows, batched into ONE call:
        # left covers local [-pad, 2·pad) -> valid [0, pad);
        # right covers [nxl-2·pad, nxl+pad) -> valid [nxl-pad, nxl)
        bp = jnp.stack([
            jnp.concatenate([lh_p, p[..., : 2 * pad]], axis=-1),
            jnp.concatenate([p[..., -2 * pad:], rh_p], axis=-1),
        ])
        bpp = jnp.stack([
            jnp.concatenate([lh_pp, p_prev[..., : 2 * pad]], axis=-1),
            jnp.concatenate([p_prev[..., -2 * pad:], rh_pp], axis=-1),
        ])
        bv = jnp.stack([v2e[:, : 3 * pad], v2e[:, nxl - pad:]])
        bs = jnp.stack([spe[:, : 3 * pad], spe[:, nxl - pad:]])
        wx0s = jnp.array([-pad, nxl - 2 * pad], jnp.int32)
        return jax.vmap(window, in_axes=(0, 0, 0, 0, 0, None, None))(
            bp, bpp, bv, bs, wx0s, x0, srcv
        )

    def stitch(bnd, mid, axis=-1):
        # stitch the disjoint valid regions
        sl = [slice(None)] * (bnd.ndim - 1)
        sl[axis] = slice(pad, 2 * pad)
        mi = [slice(None)] * mid.ndim
        mi[axis] = slice(pad, nxl - pad)
        return jnp.concatenate(
            [bnd[0][tuple(sl)], mid[tuple(mi)], bnd[1][tuple(sl)]],
            axis=axis,
        )

    def local_block(p, p_prev, v2e, spe, t0):
        # p (S, NZ, NXl) local stripe; v2e/spe (1, NZ, NXl + 2·pad)
        v2e, spe = v2e[0], spe[0]
        x0 = jax.lax.axis_index("stripe") * nxl   # global x of column 0
        srcv = make_srcv(t0)

        # 1) packed halo exchange, issued FIRST
        lh_p, rh_p, lh_pp, rh_pp = exchange_edges(
            p[..., -pad:], p[..., :pad],
            p_prev[..., -pad:], p_prev[..., :pad],
        )

        if schedule == "fused":
            # comm-avoiding only: ONE window over the extended stripe
            # [-pad, nxl+pad); its zero-extension creep exactly eats
            # the halos, leaving [0, nxl) valid after k steps
            pe, ppe, tre = window(
                jnp.concatenate([lh_p, p, rh_p], axis=-1),
                jnp.concatenate([lh_pp, p_prev, rh_pp], axis=-1),
                v2e, spe, -pad, x0, srcv,
            )
            sl = (Ellipsis, slice(pad, pad + nxl))
            return pe[sl], ppe[sl], tre[sl]

        # 2) INTERIOR (no halo dependency) while the exchange flies;
        # 3) boundary windows consume the received halos; 4) stitch
        pi, ppi, tri = interior(p, p_prev, v2e, spe, x0, srcv)
        pb, ppb, trb = boundary(
            p, p_prev, lh_p, rh_p, lh_pp, rh_pp, v2e, spe, x0, srcv
        )
        return stitch(pb, pi), stitch(ppb, ppi), stitch(trb, tri)

    def local_prologue(p, p_prev):
        # eager packed exchange priming the pipeline's halo carry for
        # block 0 — the only on-critical-path exchange of the whole scan
        return jnp.stack(exchange_edges(
            p[..., -pad:], p[..., :pad],
            p_prev[..., -pad:], p_prev[..., :pad],
        ))

    def local_pipeline_block(p, p_prev, v2e, spe, t0, halos):
        # halos (4, S, NZ, pad): [lh_p, rh_p, lh_pp, rh_pp] carried from
        # the PREVIOUS block's exchange, already in flight a full block
        v2e, spe = v2e[0], spe[0]
        x0 = jax.lax.axis_index("stripe") * nxl
        srcv = make_srcv(t0)
        lh_p, rh_p, lh_pp, rh_pp = halos[0], halos[1], halos[2], halos[3]

        # 1) BOUNDARY first: its valid columns [pad, 2·pad) are exactly
        # the stripe's fresh edge columns after this block
        pb, ppb, trb = boundary(
            p, p_prev, lh_p, rh_p, lh_pp, rh_pp, v2e, spe, x0, srcv
        )
        # 2) issue block b+1's packed ppermute from those fresh edges —
        # BEFORE the interior compute and the seam stitch, so the
        # exchange hides behind a whole block of work
        nh = exchange_edges(
            pb[1][..., pad: 2 * pad], pb[0][..., pad: 2 * pad],
            ppb[1][..., pad: 2 * pad], ppb[0][..., pad: 2 * pad],
        )
        # 3) interior — the big fusion the in-flight exchange rides over
        pi, ppi, tri = interior(p, p_prev, v2e, spe, x0, srcv)
        # 4) stitch; the fresh halos join the scan carry
        return (stitch(pb, pi), stitch(ppb, ppi), stitch(trb, tri),
                jnp.stack(nh))

    field = P(None, None, "stripe")
    parts = P("stripe", None, None)
    halo_sp = P(None, None, None, "stripe")
    # pallas_call has no replication-checking rule; the bodies are
    # replication-safe by construction (everything is stripe-local)
    sms = {}
    if schedule == "pipeline":
        sms["prologue"] = jax.shard_map(
            local_prologue, mesh=mesh, in_specs=(field, field),
            out_specs=halo_sp, check_vma=False,
        )
        sms["pipeline"] = jax.shard_map(
            local_pipeline_block, mesh=mesh,
            in_specs=(field, field, parts, parts, P(), halo_sp),
            out_specs=(field, field, field, halo_sp), check_vma=False,
        )
    else:
        sms["block"] = jax.shard_map(
            local_block, mesh=mesh,
            in_specs=(field, field, parts, parts, P()),
            out_specs=(field, field, field), check_vma=False,
        )

    def place(state_fields):
        return jax.device_put(state_fields, sh)

    return sms, v2e_all, spe_all, place, k


@resolves_use_pallas
@functools.lru_cache(maxsize=32)
def make_sharded_multistep(cfg: FWIConfig, mesh: Mesh, *, k: int = 1,
                           use_pallas: bool | None = None,
                           bz: int | None = None,
                           overlap: bool | str | None = None):
    """Temporally-blocked, comm/compute-overlapped sharded propagator.

    Returns (block_step, place): ``block_step(p, p_prev, t0)`` advances
    ALL k timesteps with a single packed halo exchange and returns
    (p, p_prev, traces) with traces (S, k, NX).  Fields are (S, NZ, NX)
    sharded on x over "stripe".  ``overlap`` takes the legacy bool
    (True="overlap", False="fused") or a schedule name; ``None``
    auto-selects per backend (``pick_schedule``).  The cross-block
    "pipeline" schedule needs a scan to carry halos through, so the
    single-block API maps it to its within-block form, "overlap".

    The requested k may be clamped so the overlap fits in one stripe
    (``effective_block``); callers advancing t0 must use the EFFECTIVE
    block size, exposed as ``block_step.k``.
    """
    schedule = _as_schedule(overlap)
    if schedule == "pipeline":
        schedule = "overlap"
    sms, v2e_all, spe_all, place, k = _sharded_block_parts(
        cfg, mesh, k, use_pallas, bz, schedule
    )
    sm = sms["block"]

    jit_block = jax.jit(
        lambda p, p_prev, t0: sm(p, p_prev, v2e_all, spe_all, t0)
    )

    def block_step(p, p_prev, t0):
        return jit_block(p, p_prev, t0)

    block_step.k = k
    return block_step, place


@resolves_use_pallas
@functools.lru_cache(maxsize=32)
def make_sharded_step(cfg: FWIConfig, mesh: Mesh, *,
                      use_pallas: bool | None = None):
    """Single-timestep sharded propagator (k=1 temporal block) — the
    seed-compatible interface: step(p, p_prev, t) -> (p, p_prev, trace)
    with trace (S, NX)."""
    block_step, place = make_sharded_multistep(
        cfg, mesh, k=1, use_pallas=use_pallas
    )

    @jax.jit
    def step(p, p_prev, t):
        pn, pp, tr = block_step(p, p_prev, t)
        return pn, pp, tr[:, 0]

    return step, place


@resolves_use_pallas
@functools.lru_cache(maxsize=32)
def make_sharded_scan_runner(cfg: FWIConfig, mesh: Mesh, *, k: int = 4,
                             use_pallas: bool | None = None,
                             bz: int | None = None,
                             overlap: bool | str | None = None):
    """Scan-fused, overlapped, temporally-blocked runner:
    run(p, p_prev, t0, blocks) advances blocks·k timesteps in ONE
    dispatch (a lax.scan over k-step fused blocks, one packed halo
    exchange per block).  ``overlap`` takes the legacy bool or a
    schedule name ("fused"/"overlap"/"pipeline"); ``None`` auto-selects
    per backend (``pick_schedule`` — "pipeline" where collectives are
    async).  Under "pipeline" the halos ride in the scan CARRY: a
    prologue exchange primes block 0, each block issues block b+1's
    ppermute before its own interior compute and stitch, and the last
    block's exchange is discarded (one wasted epilogue message —
    the price of keeping every other exchange a full block ahead).
    Returns (p, p_prev, traces (S, blocks·k, NX))."""
    schedule = _as_schedule(overlap)
    sms, v2e_all, spe_all, place, k = _sharded_block_parts(
        cfg, mesh, k, use_pallas, bz, schedule
    )

    if schedule == "pipeline":
        sm_pro, sm_pipe = sms["prologue"], sms["pipeline"]

        @functools.partial(jax.jit, static_argnames=("blocks",))
        def run(p, p_prev, t0, blocks: int):
            halos = sm_pro(p, p_prev)

            def body(carry, b):
                p, pp, h = carry
                pn, pd, tr, hn = sm_pipe(
                    p, pp, v2e_all, spe_all, t0 + b * k, h
                )
                return (pn, pd, hn), tr

            (p, pp, _), traces = jax.lax.scan(
                body, (p, p_prev, halos), jnp.arange(blocks)
            )
            # (blocks, S, k, NX) -> (S, blocks·k, NX)
            traces = jnp.moveaxis(traces, 0, 1)
            traces = traces.reshape(
                traces.shape[0], -1, traces.shape[-1]
            )
            return p, pp, traces
    else:
        sm = sms["block"]

        @functools.partial(jax.jit, static_argnames=("blocks",))
        def run(p, p_prev, t0, blocks: int):
            def body(carry, b):
                p, pp = carry
                pn, pd, tr = sm(p, pp, v2e_all, spe_all, t0 + b * k)
                return (pn, pd), tr

            (p, pp), traces = jax.lax.scan(
                body, (p, p_prev), jnp.arange(blocks)
            )
            # (blocks, S, k, NX) -> (S, blocks·k, NX)
            traces = jnp.moveaxis(traces, 0, 1)
            traces = traces.reshape(
                traces.shape[0], -1, traces.shape[-1]
            )
            return p, pp, traces

    return run, place, k


def halo_bytes_per_step(cfg: FWIConfig, n_stripes: int, k: int = 1) -> int:
    """Per-seam traffic amortized per timestep — the paper's 21 KB
    message-size claim analogue.  k=1 exchanges only the p edges; k>1
    packs p and p_prev edges into the same (k·HALO-wide) message.
    Delegates to ``halo_exchange_plan`` so the effective-block clamp
    applies here too."""
    return int(halo_exchange_plan(cfg, n_stripes, k)["bytes_per_step"])


def halo_exchange_plan(cfg: FWIConfig, n_stripes: int, k: int = 1) -> dict:
    """Seam-traffic + overlap model for the burst planner / benches.

    Beyond the message bookkeeping, exports the comm/compute-overlap
    shape of the k-step block (DESIGN.md §13): ``overlap_fraction`` is
    the share of the block's column-work that is INDEPENDENT of the
    exchange (the interior window) and can therefore hide the seam —
    ``OverheadModel.with_overlapped_seam`` turns it plus a measured
    ppermute latency into the effective (un-hidden) seam residue.
    ``redundant_frac`` is the extra trapezoid compute the boundary
    windows pay (4·k·HALO of 2·k·HALO patched columns) relative to the
    stripe width."""
    k = effective_block(cfg, n_stripes, k)
    pad = k * HALO
    nxl = cfg.nx // n_stripes
    fields = 1 if k == 1 else 2
    per_exchange = 2 * fields * pad * cfg.nz * cfg.n_shots * 4
    interior_cols = nxl                   # overlappable with the seam
    boundary_cols = 2 * 3 * pad           # two 3·k·HALO windows, after
    return {
        "k": k,
        "steps_per_exchange": k,
        "ppermutes_per_exchange": 2,
        "ppermutes_per_step": 2.0 / k,
        "bytes_per_exchange": per_exchange,
        "bytes_per_step": per_exchange / k,
        "interior_cols": interior_cols,
        "boundary_cols": boundary_cols,
        "overlap_fraction": interior_cols / (interior_cols + boundary_cols),
        "redundant_frac": 4.0 * pad / nxl,
    }
