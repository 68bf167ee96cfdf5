"""FWI forward modeling — the paper's target application (§3.1).

2-D acoustic wave propagation over a layered velocity model with a salt
body, Ricker-wavelet point sources ("shots" from the acquisition ship),
receiver traces sampled at the surface.  Multiple shots are independent
(task-parallel) over the same velocity model (data-parallel) — exactly
the structure the paper exploits to split work between environments.

Propagation engine layout:

* ``make_block_runner``  — ``lax.scan`` over k-step fused blocks
                           (``kernels.stencil.ops.wave_block``), each
                           block one fused region — source injection,
                           sponge damping and receiver capture in the
                           step epilogue, the damped previous field
                           folded into the next leapfrog expression
                           instead of materialized per step, and (XLA
                           path) the field held padded across inner
                           steps.  Bit-identical to k sequential
                           reference steps; this is what
                           ``run_forward`` uses (DESIGN.md §13).
* ``make_shot_parallel_runner`` — the same body with the shot axis
                           sharded over devices.
* model-building (``velocity_model``/``sponge_taper``/``ricker``) and
  all runner factories are memoized on the (frozen, hashable)
  ``FWIConfig`` plus their full engine knobs — ``make_block_runner``
  keys on ``(cfg, k, bz, use_pallas, collect_traces, ...)`` so
  variants never collide in the cache, and a RESHARD-triggered session
  rebuild re-uses the cached arrays and compiled runners instead of
  recomputing and re-tracing.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.stencil.ops import (
    pick_k,
    resolves_use_pallas,
    wave_block,
)
from repro.launch.mesh import make_mesh


@dataclasses.dataclass(frozen=True)
class FWIConfig:
    nz: int = 600                 # paper Table 2: 600 x 600 grid
    nx: int = 600
    dt: float = 5e-4              # s
    dx: float = 5.0               # m
    timesteps: int = 600
    n_shots: int = 4              # paper Table 2: 4 shots
    sponge_width: int = 32
    sponge_strength: float = 0.0125
    source_freq: float = 12.0     # Hz Ricker
    receiver_depth: int = 2

    def shot_positions(self) -> np.ndarray:
        xs = np.linspace(self.nx * 0.2, self.nx * 0.8, self.n_shots)
        return np.stack(
            [np.full(self.n_shots, 4.0), xs], axis=1
        ).astype(np.int32)


@functools.lru_cache(maxsize=64)
def velocity_model(cfg: FWIConfig) -> jnp.ndarray:
    """Layered model with a salt dome (paper Fig. 3 bottom).  Memoized:
    session rebuilds after RESHARD reuse the device array."""
    z = np.arange(cfg.nz)[:, None]
    x = np.arange(cfg.nx)[None, :]
    v = 1500.0 + 2.2 * z                       # depth gradient, m/s
    for depth, dv in ((cfg.nz // 3, 400.0), (cfg.nz // 2, 500.0)):
        v = v + dv * (z > depth)
    # salt dome: high-velocity ellipse
    cz, cx = int(cfg.nz * 0.62), int(cfg.nx * 0.5)
    dome = ((z - cz) / (0.18 * cfg.nz)) ** 2 + (
        (x - cx) / (0.25 * cfg.nx)
    ) ** 2 < 1.0
    v = np.where(dome, 4500.0, v)
    return jnp.asarray(v, jnp.float32)


@functools.lru_cache(maxsize=64)
def sponge_taper(cfg: FWIConfig) -> jnp.ndarray:
    w = cfg.sponge_width
    z = np.arange(cfg.nz)[:, None] + np.zeros((1, cfg.nx))
    x = np.arange(cfg.nx)[None, :] + np.zeros((cfg.nz, 1))
    dist = np.minimum.reduce([
        z, cfg.nz - 1 - z, x, cfg.nx - 1 - x,
        np.full((cfg.nz, cfg.nx), float(w)),
    ])
    taper = np.exp(-(cfg.sponge_strength * (w - dist)) ** 2)
    return jnp.asarray(np.where(dist >= w, 1.0, taper), jnp.float32)


@functools.lru_cache(maxsize=64)
def ricker(cfg: FWIConfig) -> jnp.ndarray:
    t = np.arange(cfg.timesteps) * cfg.dt
    t0 = 1.2 / cfg.source_freq
    a = (np.pi * cfg.source_freq * (t - t0)) ** 2
    return jnp.asarray((1 - 2 * a) * np.exp(-a) * 1e3, jnp.float32)


@dataclasses.dataclass
class ShotState:
    """Propagation state for a batch of shots — the checkpointable unit
    (paper Fig.1 step 2 saves exactly this)."""

    p: jnp.ndarray        # (S, NZ, NX)
    p_prev: jnp.ndarray
    t: int

    @staticmethod
    def init(cfg: FWIConfig) -> "ShotState":
        shape = (cfg.n_shots, cfg.nz, cfg.nx)
        return ShotState(
            p=jnp.zeros(shape, jnp.float32),
            p_prev=jnp.zeros(shape, jnp.float32),
            t=0,
        )


def _block_scan_body(cfg: FWIConfig, k: int, use_pallas: bool,
                     bz: int | None, collect_traces: bool,
                     stream: bool | None = None,
                     vmem_budget: int | None = None,
                     shot_tile: int | None = None):
    """Shared scan-over-fused-blocks body: local_run(p, p_prev, src_z,
    src_x, t0, steps static) -> (p, p_prev[, traces]) — UNJITTED, so
    both the single-host and the shot-sharded runner jit at their own
    boundary.  Source positions are arguments (not closure) so a
    shot-sharded caller can pass its local shard's sources.

    The whole shot batch advances through ONE shot-batched
    ``wave_block`` call per block (DESIGN.md §17) —
    not a ``vmap`` over per-shot kernels — so the shared model fields
    are read once per strip for all local shots and the batch costs one
    kernel launch per block.  Bit-identical to the old vmapped body on
    the XLA path (``wave_block_shots_ref`` is pinned bitwise vs
    ``vmap``-of-``wave_block_ref``)."""
    v = velocity_model(cfg)
    v2dt2 = (v * cfg.dt / cfg.dx) ** 2
    sponge = sponge_taper(cfg)
    wavelet = ricker(cfg)

    def block(p, p_prev, src_z, src_x, t0b, kk: int):
        srcv = wavelet[
            jnp.clip(t0b + jnp.arange(kk), 0, cfg.timesteps - 1)
        ] * (cfg.dt ** 2)
        return wave_block(
            p, p_prev, v2dt2, sponge, srcv, src_z, src_x,
            receiver_row=cfg.receiver_depth,
            use_pallas=use_pallas, bz=bz,
            stream=stream, vmem_budget=vmem_budget,
            shot_tile=shot_tile,
        )

    def local_run(p, p_prev, src_z, src_x, t0, steps: int):
        blocks, tail = divmod(steps, k)

        def body(carry, b):
            pc, pp = carry
            pn, pd, tr = block(pc, pp, src_z, src_x, t0 + b * k, k)
            return (pn, pd), (tr if collect_traces else None)

        traces = jnp.zeros((p.shape[0], 0, cfg.nx), jnp.float32)
        if blocks:
            (p, p_prev), trs = jax.lax.scan(
                body, (p, p_prev), jnp.arange(blocks)
            )
            if collect_traces:
                # (blocks, S, k, NX) -> (S, blocks*k, NX)
                trs = jnp.moveaxis(trs, 0, 1)
                traces = trs.reshape(trs.shape[0], -1, trs.shape[-1])
        if tail:
            p, p_prev, tr = block(
                p, p_prev, src_z, src_x, t0 + blocks * k, tail
            )
            if collect_traces:
                traces = jnp.concatenate([traces, tr], axis=1)
        if collect_traces:
            return p, p_prev, traces
        return p, p_prev

    return local_run


@resolves_use_pallas
@functools.lru_cache(maxsize=64)
def make_block_runner(cfg: FWIConfig, *, k: int | None = None,
                      use_pallas: bool | None = None,
                      bz: int | None = None,
                      collect_traces: bool = True,
                      stream: bool | None = None,
                      vmem_budget: int | None = None,
                      shot_tile: int | None = None):
    """jit-once FUSED multi-step propagator: ``lax.scan`` over k-step
    fused blocks (one ``wave_block`` per block — DESIGN.md §13).

    run(p, p_prev, t0, steps) -> (p, p_prev, traces (S, steps, NX))

    ``t0`` is traced, ``steps`` static; a non-multiple-of-k step count
    runs a tail block of the remainder length.  Bit-identical to k
    sequential ``wave_step_ref`` steps on the XLA path (the block body
    is a pure re-scheduling of the same ops — and the auto-selected
    STREAMED tiling for production grids keeps that contract via
    ``wave_block_shots_strips_ref``, see DESIGN.md §15).  Memoized on
    the FULL knob set (cfg, k, bz, use_pallas, collect_traces, stream,
    vmem_budget, shot_tile) so variants don't collide in the cache."""
    if k is None:
        k = pick_k(cfg.nz)
    pos = cfg.shot_positions()
    src_z = jnp.asarray(pos[:, 0])
    src_x = jnp.asarray(pos[:, 1])
    local_run = _block_scan_body(cfg, k, use_pallas, bz, collect_traces,
                                 stream, vmem_budget, shot_tile)

    @functools.partial(jax.jit, static_argnames=("steps",))
    def run(p, p_prev, t0, steps: int):
        return local_run(p, p_prev, src_z, src_x, t0, steps)

    run.k = k
    return run


@resolves_use_pallas
@functools.lru_cache(maxsize=16)
def make_shot_parallel_runner(cfg: FWIConfig, n_devices: int, *,
                              k: int | None = None,
                              use_pallas: bool | None = None,
                              bz: int | None = None,
                              collect_traces: bool = True,
                              stream: bool | None = None,
                              vmem_budget: int | None = None,
                              shot_tile: int | None = None):
    """Fused block runner with the SHOT axis sharded over devices — the
    paper's FIRST-level task-parallel split (§3.1: shots are
    independent), realized on the fused engine (DESIGN.md §13).

    Zero communication: each device owns its whole-domain shot shard
    and runs the identical scan-over-fused-blocks body on it (one
    shot-batched kernel per block — DESIGN.md §17), so parallel
    efficiency is bounded only by the host (no halos, no redundant
    columns — the complementary axis to the striped γ-split in
    fwi/domain.py, which is what cross-ENVIRONMENT placement needs).
    Returns (run, place): run(p, p_prev, t0, steps) as make_block_runner;
    place() shards the (S, NZ, NX) fields on shot axis 0.

    UNEVEN shot splits are supported by remainder placement: when
    ``n_shots % n_devices != 0`` the batch is padded to the next
    multiple by replicating shot 0 (positions included), the padded
    shots propagate as throwaway duplicates, and every output is sliced
    back to the real ``n_shots`` — so an elastic GROW to a non-divisor
    device count (4 shots → 3 devices) runs instead of crashing, at the
    cost of the duplicates' compute.  ``place`` accepts either padded
    or unpadded fields; ``run`` pads unpadded inputs itself.

    Contract: matches the single-host block runner to f32-ULP
    `allclose` (~1e-7 relative), NOT bitwise — the smaller per-device
    batch changes XLA's vectorization/FMA contraction of the stencil
    fusions.  (The striped runner keeps the batch intact and stays
    bitwise; this one trades that for perfect parallel efficiency.)"""
    from jax.sharding import NamedSharding, PartitionSpec as P

    if k is None:
        k = pick_k(cfg.nz)
    pad = (-cfg.n_shots) % n_devices     # remainder-placement padding
    mesh = make_mesh((n_devices,), ("shot",))
    pos = cfg.shot_positions()
    if pad:
        pos = np.concatenate([pos, np.repeat(pos[:1], pad, axis=0)])
    src_z = jnp.asarray(pos[:, 0])
    src_x = jnp.asarray(pos[:, 1])
    local_run = _block_scan_body(cfg, k, use_pallas, bz, collect_traces,
                                 stream, vmem_budget, shot_tile)
    out_specs = (
        (P("shot"), P("shot"), P("shot")) if collect_traces
        else (P("shot"), P("shot"))
    )

    def _pad_shots(f):
        if pad and f.shape[0] == cfg.n_shots:
            f = jnp.concatenate([f, jnp.repeat(f[:1], pad, axis=0)])
        return f

    @functools.partial(jax.jit, static_argnames=("steps",))
    def run(p, p_prev, t0, steps: int):
        p, p_prev = _pad_shots(p), _pad_shots(p_prev)
        sm = jax.shard_map(
            lambda a, b, sz, sx, t: local_run(a, b, sz, sx, t, steps),
            mesh=mesh,
            in_specs=(P("shot"), P("shot"), P("shot"), P("shot"), P()),
            out_specs=out_specs,
            check_vma=False,
        )
        out = sm(p, p_prev, src_z, src_x, t0)
        if pad:
            out = tuple(o[:cfg.n_shots] for o in out)
        return out

    sh = NamedSharding(mesh, P("shot"))

    def place(state_fields):
        padded = jax.tree_util.tree_map(_pad_shots, state_fields)
        return jax.device_put(padded, sh)

    run.k = k
    return run, place


def run_forward(cfg: FWIConfig, *, use_pallas: bool | None = None,
                state: ShotState | None = None,
                steps: int | None = None, k: int | None = None):
    """Propagate `steps` timesteps (default: to completion) through the
    fused block runner.  Returns (state, traces (S, T, NX) for the
    steps actually run)."""
    st = state or ShotState.init(cfg)
    steps = steps if steps is not None else cfg.timesteps - st.t
    if steps <= 0:
        return st, jnp.zeros((cfg.n_shots, 0, cfg.nx), jnp.float32)
    run = make_block_runner(cfg, k=k, use_pallas=use_pallas,
                            collect_traces=True)
    p, pp, traces = run(st.p, st.p_prev, st.t, steps)
    return ShotState(p=p, p_prev=pp, t=st.t + steps), traces
