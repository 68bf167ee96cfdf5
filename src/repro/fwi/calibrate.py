"""Empirical calibration — paper §3.2 (eqs. 6, 7, 8).

Two fits, same methodology as the paper's pre-processing phase:

* t(γ): execution time vs domain width — REAL wall-clock measurements of
  the solver on this machine over a sweep of widths (paper Fig. 5).  The
  linear model (eq. 4) is fitted with gamma.GammaModel.

* L(c): log-time vs chip count for each environment (paper Fig. 4).  A
  single CPU core cannot vary real chip counts, so the samples come from
  the measured single-device step time scaled by c and by the
  environment slowdown K — the *fitting code path* is identical to what
  runs on real hardware (DESIGN.md §10 records this boundary).
"""
from __future__ import annotations

import time

import numpy as np

from repro.core.capacity import LogCapacityModel
from repro.core.gamma import GammaModel
from repro.fwi.solver import FWIConfig, run_forward


def measure_gamma_sweep(
    base: FWIConfig,
    widths: list[int],
    *,
    steps: int = 30,
    repeats: int = 2,
) -> tuple[list[int], list[float]]:
    """REAL wall-clock: time `steps` timesteps at each domain width.

    Uses the scanned (jit-once) propagator so python dispatch overhead
    does not pollute the per-step estimate (the paper's fit assumes
    compute-dominated steps)."""
    import jax

    from repro.fwi.solver import ShotState, make_block_runner

    times = []
    for nx in widths:
        cfg = FWIConfig(
            nz=base.nz, nx=nx, dt=base.dt, dx=base.dx,
            timesteps=steps, n_shots=base.n_shots,
            sponge_width=base.sponge_width,
        )
        runner = make_block_runner(cfg, collect_traces=False)
        st = ShotState.init(cfg)
        jax.block_until_ready(runner(st.p, st.p_prev, 0, steps))  # compile
        best = float("inf")
        for _ in range(repeats):
            t0 = time.monotonic()
            jax.block_until_ready(runner(st.p, st.p_prev, 0, steps))
            best = min(best, time.monotonic() - t0)
        times.append(best / steps)
    return widths, times


def fit_gamma_model(base: FWIConfig, widths=None, **kw) -> GammaModel:
    widths = widths or [128, 192, 256, 384, 512]
    g, t = measure_gamma_sweep(base, widths, **kw)
    return GammaModel.fit(g, t, name="fwi-width")


def measure_seam_latency(
    cfg: FWIConfig | None = None,
    *,
    n_stripes: int = 2,
    k: int = 4,
    iters: int = 30,
    blocks: int = 8,
) -> dict:
    """REAL seam probe feeding ``OverheadModel.with_overlapped_seam``.

    Two measurements with the exact shapes the sharded engine uses:

    * ``ppermute_latency_s`` — median wall time of one jitted packed
      halo ``ppermute`` over the real ``bytes_per_exchange`` payload of
      ``halo_exchange_plan(cfg, n_stripes, k)``, on a stripe mesh of
      ``min(n_stripes, len(jax.devices()))`` devices.  With a
      multi-device mesh this is a genuine CROSS-DEVICE transfer (the
      number the pipeline schedule must hide); on one device it
      degrades to the dispatch-latency floor — ``mesh_devices`` in the
      returned dict records which one was measured.
    * ``interior_compute_s_per_step`` — measured per-step time of the
      stripe-INTERIOR fused block (the k-step ``wave_block`` window at
      the stripe-local width ``nx / n_stripes``), i.e. the compute the
      in-flight exchange can hide behind.

    The returned dict is the provenance-carrying input of
    ``sim.scenarios.overheads_from_probe`` (committed there as a
    literal snapshot so the sim layer stays jax-free) and of the
    measured-vs-modeled seam rows in ``benchmarks/bench_overheads.py``
    (DESIGN.md §15)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.fwi.domain import halo_exchange_plan, stripe_mesh
    from repro.fwi.solver import ShotState, make_block_runner

    cfg = cfg or FWIConfig()
    plan = halo_exchange_plan(cfg, n_stripes, k=k)
    k = int(plan["k"])                     # effective (clamped) block
    n_mesh = max(min(n_stripes, len(jax.devices())), 1)
    mesh = stripe_mesh(n_mesh)
    perm = [(i, (i + 1) % n_mesh) for i in range(n_mesh)]
    words = max(int(plan["bytes_per_exchange"]) // 4, 1)

    f = jax.jit(jax.shard_map(
        lambda x: jax.lax.ppermute(x, "stripe", perm),
        mesh=mesh, in_specs=P("stripe"), out_specs=P("stripe"),
    ))
    x = jnp.zeros((words * n_mesh,), jnp.float32)  # per-device payload
    f(x).block_until_ready()                       # compile
    ts = []
    for _ in range(iters):
        t0 = time.monotonic()
        f(x).block_until_ready()
        ts.append(time.monotonic() - t0)
    t_pp = sorted(ts)[len(ts) // 2]

    icfg = FWIConfig(
        nz=cfg.nz, nx=cfg.nx // n_stripes, dt=cfg.dt, dx=cfg.dx,
        timesteps=cfg.timesteps, n_shots=cfg.n_shots,
        sponge_width=cfg.sponge_width,
    )
    st = ShotState.init(icfg)
    blk = make_block_runner(icfg, k=k, collect_traces=False)
    steps = k * blocks
    jax.block_until_ready(blk(st.p, st.p_prev, 0, steps))  # compile
    best = float("inf")
    for _ in range(2):
        t0 = time.monotonic()
        jax.block_until_ready(blk(st.p, st.p_prev, 0, steps))
        best = min(best, time.monotonic() - t0)
    t_int = best / steps

    return {
        "plan": plan,
        "ppermute_latency_s": t_pp,
        "interior_compute_s_per_step": t_int,
        "n_stripes": n_stripes,
        "mesh_devices": n_mesh,
        "backend": jax.default_backend(),
    }


def measure_single_device_step(cfg: FWIConfig, steps: int = 30) -> float:
    run_forward(cfg, steps=2)
    t0 = time.monotonic()
    run_forward(cfg, steps=steps)
    return (time.monotonic() - t0) / steps


def fit_capacity_models(
    cfg: FWIConfig,
    *,
    chip_counts=(8, 16, 32, 64, 128, 256),
    cloud_slowdown: float = 1.4,
    noise: float = 0.01,
    seed: int = 0,
    measured_step_s: float | None = None,
) -> tuple[LogCapacityModel, LogCapacityModel, dict]:
    """Fit eqs. 6-7.  Samples = measured 1-device step time / c (ideal
    data-parallel scaling of the striped solver) × environment slowdown,
    with measurement noise — simulated scaling, real fitting path."""
    t1 = measured_step_s or measure_single_device_step(cfg)
    rng = np.random.default_rng(seed)
    cs = list(chip_counts)
    t_cluster = [
        t1 / c * (1.0 + noise * abs(rng.standard_normal())) for c in cs
    ]
    t_cloud = [
        t1 / c * cloud_slowdown * (1.0 + noise * abs(rng.standard_normal()))
        for c in cs
    ]
    cluster = LogCapacityModel.fit(cs, t_cluster, "fwi-cluster")
    cloud = LogCapacityModel.fit(cs, t_cloud, "fwi-cloud")
    samples = {
        "chips": cs, "t_cluster": t_cluster, "t_cloud": t_cloud,
        "t1_measured": t1,
    }
    return cluster, cloud, samples
