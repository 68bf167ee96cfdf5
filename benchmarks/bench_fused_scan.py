"""Overlap-and-fuse propagation engine vs its two ancestors.

Engines, measured on the paper's 600×600 / 4-shot geometry:

* seed loop — ONE jitted step per timestep dispatched from Python with
  the roll-based laplacian and host-side trace stacking (reproduced
  verbatim as the baseline).
* PR 1 scan — single ``lax.scan`` dispatch, unrolled per-step body,
  pad-slice laplacian, in-scan traces (``make_scan_runner``).
* fused block — ``make_block_runner``: scan over k-step fused
  ``wave_block`` regions (field padded across inner steps, damped
  previous folded into the leapfrog, epilogue-fused injection/traces).
* sharded fused — the full overlap-and-fuse engine
  (``make_sharded_scan_runner``): fused blocks per stripe, one packed
  halo exchange per block issued before the interior compute.  With ≥ 2
  host devices the stripes run on real parallel XLA executables — the
  configuration recorded in BENCH_fwi.json.
* shot-parallel fused — ``make_shot_parallel_runner``: the paper's
  first-level task-parallel split (independent shots) on the fused
  block body; zero communication, so it bounds what the host's cores
  can give the engine.

Timing is INTERLEAVED round-robin (machine-wide throughput drift on a
shared host hits every engine equally) and best-of is reported.  The
HBM-traffic proxy is ``hlo_cost.entry_boundary_bytes``: wavefield bytes
crossing the jit boundary per step — a k-step fused block moves the
fields once per k steps (the per-op cost_analysis sum cannot see this).
CPU wall numbers; relative ratios are the deliverable.
"""
from __future__ import annotations

import os
import sys

# 2 host devices so the striped engine measures real parallelism; must
# precede the first jax import (harmless no-op on real accelerators)
if "jax" not in sys.modules:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=2"
        ).strip()

import functools  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.fwi.domain import (  # noqa: E402
    halo_exchange_plan,
    make_sharded_scan_runner,
    pick_schedule,
    stripe_mesh,
)
from repro.fwi.solver import (  # noqa: E402
    FWIConfig,
    ShotState,
    make_block_runner,
    make_scan_runner,
    make_shot_parallel_runner,
    ricker,
    sponge_taper,
    velocity_model,
)
from repro.kernels.stencil.ops import pick_k, wave_block, wave_step  # noqa: E402
from repro.kernels.stencil.ref import laplacian_roll  # noqa: E402
from repro.launch.hlo_cost import (  # noqa: E402
    entry_boundary_bytes,
    xla_cost_analysis,
)


def _seed_step_fn(cfg: FWIConfig):
    """The seed engine's per-timestep function, verbatim: roll-based
    laplacian, one jitted dispatch per step."""
    v = velocity_model(cfg)
    v2dt2 = (v * cfg.dt / cfg.dx) ** 2
    sponge = sponge_taper(cfg)
    wavelet = ricker(cfg)
    pos = cfg.shot_positions()
    src_z = jnp.asarray(pos[:, 0])
    src_x = jnp.asarray(pos[:, 1])

    def one_shot(p, p_prev, t, zi, xi):
        lap = laplacian_roll(p)
        p_next = (2.0 * p - p_prev + v2dt2 * lap) * sponge
        p_damped = p * sponge
        p_next = p_next.at[zi, xi].add(wavelet[t] * (cfg.dt ** 2))
        return p_next, p_damped

    @jax.jit
    def step(p, p_prev, t):
        p_next, p_damped = jax.vmap(
            one_shot, in_axes=(0, 0, None, 0, 0)
        )(p, p_prev, t, src_z, src_x)
        return p_next, p_damped, p_next[:, cfg.receiver_depth, :]

    return step


def host_parallel_scaling() -> float:
    """Measured 2-process CPU scaling of THIS host right now.

    The container advertises 2 CPUs but shares a hypervisor; under
    neighbor steal, two busy processes can run SLOWER than one
    (observed 0.45×–1.9× across hours).  The sharded engines need real
    parallel cores, so every trajectory point records this probe —
    a point taken at scaling ≪ 2 understates the engine, not the code.
    """
    import subprocess

    code = "x=0\nfor i in range(2_000_000): x+=i*i"
    t0 = time.perf_counter()
    subprocess.run([sys.executable, "-c", code])
    t1 = time.perf_counter() - t0
    t0 = time.perf_counter()
    ps = [subprocess.Popen([sys.executable, "-c", code]) for _ in range(2)]
    for p in ps:
        p.wait()
    t2 = time.perf_counter() - t0
    return 2.0 * t1 / max(t2, 1e-9)


def _interleaved_best(engines: dict, rounds: int = 6) -> dict[str, float]:
    """Round-robin timing: every engine measured in every round, so
    host-wide throughput drift cancels out of the ratios."""
    for fn in engines.values():
        fn()                                   # compile
    best = {name: float("inf") for name in engines}
    for _ in range(rounds):
        for name, fn in engines.items():
            t0 = time.perf_counter()
            fn()
            best[name] = min(best[name], time.perf_counter() - t0)
    return best


def build_engines(cfg: FWIConfig, steps: int, *, stripes: int | None = None):
    """(engines dict, meta dict) for the steps/sec comparison."""
    st = ShotState.init(cfg)
    k = pick_k(cfg.nz)
    n = stripes if stripes is not None else min(2, jax.device_count())

    step = _seed_step_fn(cfg)

    def loop():
        p, pp, traces = st.p, st.p_prev, []
        for t in range(steps):
            p, pp, tr = step(p, pp, t)
            traces.append(tr)
        jax.block_until_ready(jnp.stack(traces, axis=1))

    scan_runner = make_scan_runner(cfg, collect_traces=True)

    def scan():
        jax.block_until_ready(scan_runner(st.p, st.p_prev, 0, steps))

    block_runner = make_block_runner(cfg, k=k)

    def block():
        jax.block_until_ready(block_runner(st.p, st.p_prev, 0, steps))

    engines = {
        "seed_loop": loop,
        "pr1_scan": scan,
        "fused_block": block,
    }

    def add_sharded(name, kk, overlap):
        run_s, place, keff = make_sharded_scan_runner(
            cfg, stripe_mesh(n), k=kk, overlap=overlap
        )
        ps, pps = place((st.p, st.p_prev))
        blocks = steps // keff

        def sharded(run_s=run_s, ps=ps, pps=pps, blocks=blocks):
            jax.block_until_ready(run_s(ps, pps, 0, blocks))

        engines[name] = sharded
        return keff

    # the shipped engine (schedule auto-selected per backend) at the
    # heuristic block length and half of it — block length is a tuned
    # knob, the bench records which setting carried the day
    keffs = {}
    for kk in sorted({k, max(k // 2, 1)}):
        keffs[kk] = add_sharded(f"sharded_fused_k{kk}", kk, None)
    # the overlap schedule, forced, for the record on this backend
    add_sharded(f"sharded_overlap_k{k}", k, True)

    # shot-parallel fused blocks: the paper's first-level task-parallel
    # split (shots are independent) — zero communication, so parallel
    # efficiency is bounded only by the host.  Uneven splits are legal
    # now (the runner pads the batch to the device count), so the old
    # ``n_shots % n == 0`` gate is gone.
    if n > 1:
        run_sp, place_sp = make_shot_parallel_runner(cfg, n, k=k)
        psp, ppsp = place_sp((st.p, st.p_prev))

        def shot_par():
            jax.block_until_ready(run_sp(psp, ppsp, 0, steps))

        engines[f"shot_parallel_k{k}"] = shot_par

    meta = {"k": k, "stripes": n, "k_effective": keffs,
            "sharded_variants": sorted(
                nm for nm in engines
                if nm.startswith(("sharded", "shot_parallel"))
            )}
    return engines, meta


def hbm_boundary_proxy(cfg: FWIConfig, k: int = 4) -> dict:
    """Per-step WAVEFIELD bytes crossing the launch boundary, step
    engine vs k-step fused block, via ``entry_boundary_bytes`` — the
    HBM-traffic proxy for temporal fusion (a k-step block round-trips
    the fields once per k steps).  The raw ``xla_cost_analysis``
    'bytes accessed' totals are recorded alongside for transparency:
    that per-op sum charges every fused-region intermediate identically
    inside and outside the block, so it cannot see the boundary win."""
    p = jnp.zeros((cfg.nz, cfg.nx), jnp.float32)
    v = jnp.full((cfg.nz, cfg.nx), 0.1, jnp.float32)
    s = jnp.ones((cfg.nz, cfg.nx), jnp.float32)
    srcv = jnp.zeros((k,), jnp.float32)
    f_step = jax.jit(
        lambda a, b, vv, ss: wave_step(a, b, vv, ss)
    ).lower(p, p, v, s).compile()
    f_block = jax.jit(
        lambda a, b, vv, ss, sv: wave_block(a, b, vv, ss, sv, 3, 4)
    ).lower(p, p, v, s, srcv).compile()
    shape = (cfg.nz, cfg.nx)
    step_b = entry_boundary_bytes(f_step.as_text(), shape)["total_bytes"]
    block_b = entry_boundary_bytes(f_block.as_text(), shape)["total_bytes"]
    ca_step = float(xla_cost_analysis(f_step).get("bytes accessed", 0.0))
    ca_block = float(xla_cost_analysis(f_block).get("bytes accessed", 0.0))
    return {
        "step_bytes_per_step": float(step_b),
        "block_bytes_per_step": float(block_b) / k,
        "k": k,
        "reduction_x": step_b / (block_b / k),
        "xla_cost_analysis_step_bytes": ca_step,
        "xla_cost_analysis_block_bytes_per_step": ca_block / k,
    }


def trajectory_point(cfg: FWIConfig | None = None, steps: int = 48,
                     rounds: int = 6) -> dict:
    """One perf-trajectory point (the BENCH_fwi.json payload)."""
    cfg = cfg or FWIConfig()
    engines, meta = build_engines(cfg, steps)
    best = _interleaved_best(engines, rounds=rounds)
    sps = {name: steps / t for name, t in best.items()}
    proxy = hbm_boundary_proxy(cfg, k=4)
    spd = {k: best["pr1_scan"] / t for k, t in best.items()}
    fused = {nm: s for nm, s in spd.items()
             if nm.startswith(("sharded_fused", "shot_parallel"))}
    headline = max(fused, key=fused.get) if fused else "fused_block"
    return {
        "config": {"nz": cfg.nz, "nx": cfg.nx, "n_shots": cfg.n_shots,
                   "timesteps_measured": steps},
        "host_parallel_scaling": round(host_parallel_scaling(), 2),
        "engine_meta": meta,
        "steps_per_sec": {k: round(v, 2) for k, v in sps.items()},
        "us_per_step": {k: round(t / steps * 1e6, 1)
                        for k, t in best.items()},
        "speedup_vs_pr1_scan": {k: round(v, 3) for k, v in spd.items()},
        "fused_engine": {"name": headline,
                         "speedup_vs_pr1_scan": round(spd[headline], 3)},
        "hbm_boundary_proxy": {k: round(v, 3) if isinstance(v, float)
                               else v for k, v in proxy.items()},
    }


BIG_GRIDS = ((4096, 4096), (8192, 2048))


def build_big_engines(cfg: FWIConfig, steps: int, *,
                      stripes: int | None = None):
    """Reduced engine set for production-scale grids (DESIGN.md §15).

    The seed loop / PR 1 scan ancestors are dropped (minutes per round
    at 4096² for a long-settled comparison); what matters at scale is
    resident vs STREAMED tiling and the fused vs overlap vs pipeline
    halo schedules.  Pallas-interpret streaming is correctness-only on
    CPU (the ci.sh big-grid smoke covers it); wall-clock rows here use
    the XLA mirrors of the same tilings."""
    st = ShotState.init(cfg)
    k = 4
    n = stripes if stripes is not None else min(2, jax.device_count())
    engines = {}

    for name, stream in (("fused_block_resident", False),
                         ("fused_block_streamed", True)):
        runner = make_block_runner(cfg, k=k, stream=stream,
                                   collect_traces=False)

        def fn(runner=runner):
            jax.block_until_ready(runner(st.p, st.p_prev, 0, steps))

        engines[name] = fn

    keffs = {}
    for name, sched in (("sharded_fused", "fused"),
                        ("sharded_overlap", "overlap"),
                        ("sharded_pipeline", "pipeline")):
        run_s, place, keff = make_sharded_scan_runner(
            cfg, stripe_mesh(n), k=k, overlap=sched
        )
        ps, pps = place((st.p, st.p_prev))
        blocks = steps // keff

        def sharded(run_s=run_s, ps=ps, pps=pps, blocks=blocks):
            jax.block_until_ready(run_s(ps, pps, 0, blocks))

        engines[f"{name}_k{keff}"] = sharded
        keffs[name] = keff

    meta = {"k": k, "stripes": n, "k_effective": keffs,
            "schedule_auto": pick_schedule(n)}
    return engines, meta


def big_trajectory_point(grids=BIG_GRIDS, steps: int = 8,
                         rounds: int = 2) -> dict:
    """Production-scale trajectory point: per-grid steps/sec for the
    streamed-vs-resident tilings and the three halo schedules, the HBM
    boundary proxy, and the VMEM capacity bookkeeping that motivates
    the streamed kernel (resident bytes vs budget vs streamed bytes)."""
    from repro.kernels.stencil.kernel import (
        DEFAULT_VMEM_BUDGET,
        pick_bz_stream,
        resident_vmem_bytes,
        should_stream,
        stream_vmem_bytes,
    )

    point = {
        "tier": "big",
        "host_parallel_scaling": round(host_parallel_scaling(), 2),
        "grids": {},
    }
    for nz, nx in grids:
        cfg = FWIConfig(nz=nz, nx=nx, n_shots=1,
                        timesteps=max(steps, 8))
        engines, meta = build_big_engines(cfg, steps)
        best = _interleaved_best(engines, rounds=rounds)
        base = best[f"sharded_fused_k{meta['k_effective']['sharded_fused']}"]
        k = meta["k"]
        sbz = pick_bz_stream(nz, nx, k)
        proxy = hbm_boundary_proxy(cfg, k=k)
        point["grids"][f"{nz}x{nx}"] = {
            "config": {"nz": nz, "nx": nx, "n_shots": cfg.n_shots,
                       "timesteps_measured": steps},
            "steps_per_sec": {nm: round(steps / t, 3)
                              for nm, t in best.items()},
            "us_per_step": {nm: round(t / steps * 1e6, 1)
                            for nm, t in best.items()},
            "speedup_vs_sharded_fused": {nm: round(base / t, 3)
                                         for nm, t in best.items()},
            "engine_meta": meta,
            "vmem": {
                "budget_bytes": DEFAULT_VMEM_BUDGET,
                "resident_bytes_k4": resident_vmem_bytes(nz, nx, k),
                "fits_resident": not should_stream(nz, nx, k),
                "stream_bz": sbz,
                "stream_bytes": stream_vmem_bytes(nz, nx, sbz, k),
            },
            "hbm_boundary_proxy": {kk: round(v, 3) if isinstance(v, float)
                                   else v for kk, v in proxy.items()},
        }
    return point


def _vmapped_block_runner(cfg: FWIConfig, k: int):
    """The PRE-shot-batch engine body, reconstructed for the bench:
    ``jax.vmap`` of the per-shot ``wave_block_ref`` inside the block
    scan — exactly what ``_block_scan_body`` did before DESIGN.md §17
    replaced it with one batched ``wave_block`` call.  This is the
    baseline the shot-batched engine is measured against."""
    from repro.kernels.stencil.ref import wave_block_ref

    v = velocity_model(cfg)
    v2dt2 = (v * cfg.dt / cfg.dx) ** 2
    sponge = sponge_taper(cfg)
    wavelet = ricker(cfg)
    pos = cfg.shot_positions()
    src_z = jnp.asarray(pos[:, 0])
    src_x = jnp.asarray(pos[:, 1])

    @functools.partial(jax.jit, static_argnames=("steps",))
    def run(p, p_prev, t0, steps):
        blocks = steps // k

        def body(carry, b):
            pc, pp = carry
            tt = t0 + b * k + jnp.arange(k)
            srcv = wavelet[jnp.clip(tt, 0, cfg.timesteps - 1)] \
                * (cfg.dt ** 2)

            def one(a, bb, zi, xi):
                return wave_block_ref(
                    a, bb, v2dt2, sponge, srcv, zi, xi,
                    receiver_row=cfg.receiver_depth,
                )

            pn, pd, tr = jax.vmap(one, (0, 0, 0, 0))(pc, pp, src_z, src_x)
            return (pn, pd), tr

        (p, p_prev), trs = jax.lax.scan(body, (p, p_prev),
                                        jnp.arange(blocks))
        return p, p_prev, trs

    return run


SHOT_BATCH_BIG_GRID = (1536, 1536, 2, 4)   # nz, nx, shots, k: must stream


def shot_batch_point(steps: int = 48, rounds: int = 6,
                     pallas_rounds: int = 3) -> dict:
    """Trajectory point (tier "shot_batch") for the batched engine.

    Rows come in matched batched-vs-vmapped pairs (DESIGN.md §17):

    * XLA scan runners at the paper geometry — the old vmapped block
      body vs ``make_block_runner``'s batched dispatch.  On CPU XLA
      compiles the vmapped body into the same fused loop as the
      hand-batched mirror (they are bitwise-identical), so this pair is
      expected to be a wash; it is recorded to pin that fact.
    * Pallas-interpret per-block rows — ``vmap``-of-
      ``wave_block_pallas`` (one kernel per shot) vs the batched kernel
      at the dispatch's default shot tile and the streamed full-batch
      kernel.  Here the launch/grid-pass amortization is real work
      removed (S·nz/bz passes → (S/tile)·nz/bz), so this pair carries
      the batched-beats-vmapped acceptance ratio.
    * A big-tier pair at a grid whose batch CANNOT sit resident in
      VMEM, where the streamed batched kernel is the only in-budget
      path (XLA strip mirrors for wall clock, per the big-tier
      convention, plus the interpret pair for the record).
    """
    from repro.kernels.stencil.kernel import (
        DEFAULT_VMEM_BUDGET,
        pick_bz_block,
        pick_bz_stream,
        pick_shot_tile,
        resident_vmem_bytes,
        stream_vmem_bytes,
        wave_block_pallas,
    )
    from repro.kernels.stencil.ref import (
        wave_block_shots_strips_ref,
        wave_block_strips_ref,
    )
    from repro.launch.hlo_cost import shot_batch_strip_bytes

    cfg = FWIConfig()
    S, k = cfg.n_shots, pick_k(cfg.nz)
    st = ShotState.init(cfg)

    vmapped = _vmapped_block_runner(cfg, k)
    batched = make_block_runner(cfg, k=k)
    xla = {
        "xla_vmapped": lambda: jax.block_until_ready(
            vmapped(st.p, st.p_prev, 0, steps)),
        "xla_batched": lambda: jax.block_until_ready(
            batched(st.p, st.p_prev, 0, steps)),
    }
    best = _interleaved_best(xla, rounds=rounds)
    sps = {nm: steps / t for nm, t in best.items()}

    # Pallas rows: per-block timing (interpret mode is the CPU stand-in
    # for the TPU kernel; one block = k timesteps)
    v = velocity_model(cfg)
    v2dt2 = (v * cfg.dt / cfg.dx) ** 2
    sponge = sponge_taper(cfg)
    srcv = ricker(cfg)[:k] * (cfg.dt ** 2)
    pos = cfg.shot_positions()
    sz = jnp.asarray(pos[:, 0])
    sx = jnp.asarray(pos[:, 1])
    bz = pick_bz_block(cfg.nz, k)
    tile = pick_shot_tile(S, cfg.nz, cfg.nx, k, bz=bz)
    sbz = pick_bz_stream(cfg.nz, cfg.nx, k, s=S)

    def one(a, b, zi, xi):
        return wave_block_pallas(a, b, v2dt2, sponge, srcv, zi, xi,
                                 receiver_row=cfg.receiver_depth, bz=bz)

    vm = jax.jit(jax.vmap(one, (0, 0, 0, 0)))
    pal = {
        "pallas_vmapped": lambda: jax.block_until_ready(
            vm(st.p, st.p_prev, sz, sx)),
        f"pallas_batched_tile{tile}": lambda: jax.block_until_ready(
            wave_block(st.p, st.p_prev, v2dt2, sponge, srcv, sz, sx,
                       receiver_row=cfg.receiver_depth, use_pallas=True,
                       bz=bz, stream=False)),
        f"pallas_batched_stream_s{S}": lambda: jax.block_until_ready(
            wave_block(st.p, st.p_prev, v2dt2, sponge, srcv, sz, sx,
                       receiver_row=cfg.receiver_depth, use_pallas=True,
                       stream=True, shot_tile=S)),
    }
    pbest = _interleaved_best(pal, rounds=pallas_rounds)
    sps.update({nm: k / t for nm, t in pbest.items()})
    pal_batched = {nm: s for nm, s in sps.items()
                   if nm.startswith("pallas_batched")}
    pal_head = max(pal_batched, key=pal_batched.get)

    # big tier: the batch cannot sit resident — streaming is mandatory
    bnz, bnx, bS, bk = SHOT_BATCH_BIG_GRID
    bcfg = FWIConfig(nz=bnz, nx=bnx, n_shots=bS, timesteps=max(bk, 8))
    bst = ShotState.init(bcfg)
    bv = velocity_model(bcfg)
    bv2dt2 = (bv * bcfg.dt / bcfg.dx) ** 2
    bsponge = sponge_taper(bcfg)
    bsrcv = ricker(bcfg)[:bk] * (bcfg.dt ** 2)
    bpos = bcfg.shot_positions()
    bsz = jnp.asarray(bpos[:, 0])
    bsx = jnp.asarray(bpos[:, 1])
    bsbz1 = pick_bz_stream(bnz, bnx, bk)        # per-shot strip
    bsbzS = pick_bz_stream(bnz, bnx, bk, s=bS)  # batched strip

    def big_one(a, b, zi, xi):
        return wave_block_strips_ref(a, b, bv2dt2, bsponge, bsrcv, zi, xi,
                                     receiver_row=bcfg.receiver_depth,
                                     bz=bsbz1)

    big_vm = jax.jit(jax.vmap(big_one, (0, 0, 0, 0)))
    big_batched = jax.jit(functools.partial(
        wave_block_shots_strips_ref, receiver_row=bcfg.receiver_depth,
        bz=bsbz1))
    big = {
        "xla_vmapped_strips": lambda: jax.block_until_ready(
            big_vm(bst.p, bst.p_prev, bsz, bsx)),
        "xla_batched_strips": lambda: jax.block_until_ready(
            big_batched(bst.p, bst.p_prev, bv2dt2, bsponge, bsrcv,
                        bsz, bsx)),
        "pallas_vmapped_stream": lambda: jax.block_until_ready(
            jax.tree_util.tree_map(lambda *a: jnp.stack(a), *[
                wave_block(bst.p[i], bst.p_prev[i], bv2dt2, bsponge,
                           bsrcv, bsz[i], bsx[i],
                           receiver_row=bcfg.receiver_depth,
                           use_pallas=True, stream=True, bz=bsbz1)
                for i in range(bS)])),
        "pallas_batched_stream": lambda: jax.block_until_ready(
            wave_block(bst.p, bst.p_prev, bv2dt2, bsponge, bsrcv,
                       bsz, bsx, receiver_row=bcfg.receiver_depth,
                       use_pallas=True, stream=True, shot_tile=bS,
                       bz=bsbzS)),
    }
    bbest = _interleaved_best(big, rounds=max(pallas_rounds - 1, 1))
    big_sps = {nm: bk / t for nm, t in bbest.items()}

    return {
        "tier": "shot_batch",
        "config": {"nz": cfg.nz, "nx": cfg.nx, "n_shots": S, "k": k,
                   "bz": bz, "shot_tile": tile, "stream_bz": sbz,
                   "timesteps_measured": steps},
        "host_parallel_scaling": round(host_parallel_scaling(), 2),
        "steps_per_sec": {nm: round(v, 2) for nm, v in sps.items()},
        "batched_vs_vmapped": {
            "xla": round(sps["xla_batched"] / sps["xla_vmapped"], 3),
            "pallas": round(sps[pal_head] / sps["pallas_vmapped"], 3),
            "pallas_engine": pal_head,
        },
        "vmem": {
            "budget_bytes": DEFAULT_VMEM_BUDGET,
            "resident_bytes_s1": resident_vmem_bytes(
                cfg.nz, cfg.nx, k, bz=bz),
            "resident_bytes_sS": resident_vmem_bytes(
                cfg.nz, cfg.nx, k, bz=bz, s=S),
            "resident_bytes_tile": resident_vmem_bytes(
                cfg.nz, cfg.nx, k, bz=bz, s=tile),
            "stream_bytes_sS": stream_vmem_bytes(
                cfg.nz, cfg.nx, sbz, k, s=S),
            "shot_tile": tile,
        },
        "traffic_model": {nm: val for nm, val in
                          shot_batch_strip_bytes(cfg.nz, cfg.nx, S,
                                                 k=k).items()},
        "big": {
            "config": {"nz": bnz, "nx": bnx, "n_shots": bS, "k": bk,
                       "stream_bz_s1": bsbz1, "stream_bz_sS": bsbzS},
            "steps_per_sec": {nm: round(v, 3)
                              for nm, v in big_sps.items()},
            "batched_vs_vmapped": {
                "xla": round(big_sps["xla_batched_strips"]
                             / big_sps["xla_vmapped_strips"], 3),
                "pallas": round(big_sps["pallas_batched_stream"]
                                / big_sps["pallas_vmapped_stream"], 3),
            },
            "vmem": {
                "budget_bytes": DEFAULT_VMEM_BUDGET,
                "resident_bytes_sS": resident_vmem_bytes(
                    bnz, bnx, bk, s=bS),
                "stream_bytes_sS": stream_vmem_bytes(
                    bnz, bnx, bsbzS, bk, s=bS),
            },
            "traffic_model": shot_batch_strip_bytes(bnz, bnx, bS, k=bk),
        },
    }


def run_shot_batch() -> list[str]:
    """The shot-batch tier as harness rows."""
    point = shot_batch_point()
    rows = [f"shot_batch.host_parallel_scaling,0,"
            f"{point['host_parallel_scaling']}"]
    for nm, v in point["steps_per_sec"].items():
        rows.append(f"shot_batch.{nm}_steps_per_sec,0,{v}")
    bb = point["batched_vs_vmapped"]
    rows.append(f"shot_batch.batched_vs_vmapped_xla,0,{bb['xla']}")
    rows.append(f"shot_batch.batched_vs_vmapped_pallas,0,{bb['pallas']}")
    vm = point["vmem"]
    rows.append(
        f"shot_batch.vmem,0,"
        f"tile={vm['shot_tile']};"
        f"resident_sS_mb={vm['resident_bytes_sS'] / 2**20:.1f};"
        f"tile_mb={vm['resident_bytes_tile'] / 2**20:.1f};"
        f"stream_sS_mb={vm['stream_bytes_sS'] / 2**20:.1f};"
        f"budget_mb={vm['budget_bytes'] / 2**20:.0f}"
    )
    tm = point["traffic_model"]
    rows.append(
        f"shot_batch.traffic_ratio,0,{tm['traffic_ratio']:.4f}"
    )
    for nm, v in point["big"]["steps_per_sec"].items():
        rows.append(f"shot_batch.big.{nm}_steps_per_sec,0,{v}")
    bigbb = point["big"]["batched_vs_vmapped"]
    rows.append(f"shot_batch.big.batched_vs_vmapped_pallas,0,"
                f"{bigbb['pallas']}")
    return rows


def run() -> list[str]:
    rows = []
    cfg = FWIConfig()                      # paper Table 2: 600x600, 4 shots
    steps = 48
    point = trajectory_point(cfg, steps=steps)
    sps = point["steps_per_sec"]
    us = point["us_per_step"]
    spd = point["speedup_vs_pr1_scan"]

    rows.append(f"fused_scan.loop_per_step,{us['seed_loop']:.0f},"
                f"{sps['seed_loop']:.1f}")
    rows.append(f"fused_scan.scan_per_step,{us['pr1_scan']:.0f},"
                f"{sps['pr1_scan']:.1f}")
    rows.append(f"fused_scan.speedup_x,0,"
                f"{sps['pr1_scan'] / sps['seed_loop']:.2f}")
    rows.append(f"fused_scan.block_per_step,{us['fused_block']:.0f},"
                f"{sps['fused_block']:.1f}")
    rows.append(f"fused_scan.block_speedup_x,0,{spd['fused_block']:.2f}")
    meta = point["engine_meta"]
    for nm in meta["sharded_variants"]:
        rows.append(
            f"fused_scan.{nm}_per_step,{us[nm]:.0f},"
            f"n{meta['stripes']}={sps[nm]:.1f}"
        )
    head = point["fused_engine"]
    rows.append(
        f"fused_scan.fused_engine_speedup_x,0,"
        f"{head['speedup_vs_pr1_scan']:.2f}"
    )
    rows.append(f"fused_scan.fused_engine_config,0,{head['name']}")
    proxy = point["hbm_boundary_proxy"]
    rows.append(
        f"fused_scan.hbm_boundary_step_bytes,0,"
        f"{proxy['step_bytes_per_step']:.0f}"
    )
    rows.append(
        f"fused_scan.hbm_boundary_block_k{proxy['k']}_bytes,0,"
        f"{proxy['block_bytes_per_step']:.0f}"
    )
    rows.append(
        f"fused_scan.hbm_boundary_reduction_x,0,{proxy['reduction_x']:.2f}"
    )

    # temporal blocking: ppermutes per step at k=1 vs k=4 (plan model)
    for kk in (1, 4):
        plan = halo_exchange_plan(cfg, 1, k=kk)
        rows.append(
            f"fused_scan.halo_plan_k{kk},0,"
            f"ppermutes_per_step={plan['ppermutes_per_step']};"
            f"overlap_fraction={plan['overlap_fraction']:.3f}"
        )
    return rows


def run_big() -> list[str]:
    """The --big tier as harness rows (one per engine per grid)."""
    point = big_trajectory_point()
    rows = [f"fused_scan_big.host_parallel_scaling,0,"
            f"{point['host_parallel_scaling']}"]
    for gname, g in point["grids"].items():
        for nm, us in g["us_per_step"].items():
            rows.append(
                f"fused_scan_big.{gname}.{nm},{us:.0f},"
                f"sps={g['steps_per_sec'][nm]};"
                f"vs_sharded_fused={g['speedup_vs_sharded_fused'][nm]}"
            )
        vm = g["vmem"]
        rows.append(
            f"fused_scan_big.{gname}.vmem,0,"
            f"resident_mb={vm['resident_bytes_k4'] / 2**20:.0f};"
            f"budget_mb={vm['budget_bytes'] / 2**20:.0f};"
            f"fits_resident={vm['fits_resident']};"
            f"stream_bz={vm['stream_bz']};"
            f"stream_mb={vm['stream_bytes'] / 2**20:.1f}"
        )
        rows.append(
            f"fused_scan_big.{gname}.schedule_auto,0,"
            f"{g['engine_meta']['schedule_auto']}"
        )
    return rows


if __name__ == "__main__":
    import json

    big = "--big" in sys.argv
    shot_batch = "--shot-batch" in sys.argv
    argv = [a for a in sys.argv if a not in ("--big", "--shot-batch")]
    if len(argv) > 1 and argv[1] == "--write-trajectory":
        path = argv[2] if len(argv) > 2 else "BENCH_fwi.json"
        if shot_batch:
            point = shot_batch_point()
        elif big:
            point = big_trajectory_point()
        else:
            point = trajectory_point()
        try:
            with open(path) as f:
                doc = json.load(f)
        except (FileNotFoundError, ValueError):
            doc = {"description": "FWI engine perf trajectory, one point "
                                  "per engine-touching PR", "points": []}
        doc["points"].append(point)
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"wrote {path} ({len(doc['points'])} points)")
    else:
        if shot_batch:
            rows = run_shot_batch()
        elif big:
            rows = run_big()
        else:
            rows = run()
        for row in rows:
            print(row)
