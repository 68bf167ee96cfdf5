#!/usr/bin/env python3
"""Drive the system's main paths once on TPU and check what comes out.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # one host with four chips

One chip runs three phases through the entry points a user calls:

* ``fwi_resident`` — the paper's grid (Table 2: 600², 4 shots) through
  ``fwi_session_factory`` under ``ElasticOrchestrator.run`` on one
  cluster pod with a deadline no burst is needed for; the dispatch must
  pick the resident shot-batched Pallas kernel;
* ``fwi_streamed`` — the production grid of DESIGN.md §15 (4096², 16
  shots) through the same path; the dispatch must pick the streamed
  shot-batched kernel;
* ``lm_train`` — ``repro.launch.train.main`` for 3 steps of mamba2-370m
  at its published widths (seq 1024, batch 8); the loss must be finite.

Each FWI phase prints its compile seconds, measured seconds per step,
whether the compiled runner holds a Pallas kernel (``tpu_custom_call``),
the device's peak bytes in use so far, and its largest difference from
the pure-jnp reference (``use_pallas=False``) run on the chip over the
same steps, relative to max |p|, which must be ≤ 1e-5.

``--chips 4`` runs only what exists across chips: the 4096² job striped
over four chips (1024 columns each, the "pipeline" halo schedule)
against the same job on one stripe, and an elastic run that grows from
one stripe to four under a deadline squeeze and retires back, whose
final wavefield must match the unscaled run.

Exits non-zero, printing no result, when JAX finds no TPU.  Any failed
check ends the run with a non-zero code.  Only when every phase passed
is the last line one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: the repo's Pallas-vs-reference tolerance (tests/test_shot_batch.py)
TOL = 1e-5
#: timesteps per halo exchange and per fused kernel call
K = 4
#: timesteps per measured dispatch of the FWI session
SCAN_BLOCK = 8

_compile_s = [0.0]


def check(ok: bool, what: str) -> None:
    if not ok:
        sys.exit(f"chip_smoke: FAILED: {what}")


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def tpu_devices(chips: int):
    """The TPU devices, or exit non-zero before anything runs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found platform "
                 f"{devs[0].platform!r}); nothing was run")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX found {len(devs)}")
    print(f"device platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)}", flush=True)
    return devs


def _count_compile(event: str, duration: float, **_) -> None:
    # tracing, lowering and XLA compilation of every jitted program
    if event.startswith("/jax/core/compile/"):
        _compile_s[0] += duration


def peak_bytes() -> int:
    import jax

    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


# ------------------------------------------------------------------ FWI


def orchestrated(cfg, steps: int, *, stripes: int | None = None,
                 elastic: bool = False):
    """Run ``cfg`` for ``steps`` through ``fwi_session_factory`` under
    ``ElasticOrchestrator.run``.  Without ``elastic``: one cluster pod,
    measured step times, a deadline nothing threatens.  With it: the
    deadline squeeze of the real-elastic acceptance test (modeled step
    times, the plan policy, re-striping 1 → 4 stripes while the cloud
    pod is held).  Returns (run record, every session built)."""
    from repro.core import (
        BurstPlanner, DeadlinePredictor, ElasticOrchestrator,
        LogCapacityModel, OverheadModel, PodSpec, Resources,
    )
    from repro.fwi.driver import (
        TimeModel, elastic_stripes_for, fwi_session_factory,
    )
    from repro.sim import PlanAutoscaler

    w, k_cloud, legal = 64.0, 1.4, [16, 32, 64, 128]
    cs = sorted(set(legal) | {64})
    planner = BurstPlanner(
        cluster_model=LogCapacityModel.fit(cs, [w / c for c in cs]),
        cloud_model=LogCapacityModel.fit(cs, [k_cloud * w / c for c in cs]),
        chips_cluster=64, legal_slices=legal,
        overheads=OverheadModel(ckpt_s=5.0, provision_s=10.0,
                                restart_s=5.0),
        price_per_chip_hour=3.0, cost_weight=0.5,
    )
    if elastic:
        orch = ElasticOrchestrator(
            planner=planner, predictor=DeadlinePredictor(400.0),
            check_every=8, ckpt_every=40, eval_interval_s=7.0,
            cloud_slowdown=k_cloud,
        )
        time_model = TimeModel(chip_seconds_per_step=w, jitter=0.01)
        stripes_for = elastic_stripes_for(1, 4)
        extra = dict(autoscaler=PlanAutoscaler(),
                     deadline_changes=[(20.0, 105.0), (60.0, 400.0)])
    else:
        orch = ElasticOrchestrator(
            planner=planner, predictor=DeadlinePredictor(1e9),
            ckpt_every=10 ** 9,
        )
        time_model = TimeModel(chip_seconds_per_step=None, jitter=0.0)
        stripes_for = (lambda res: stripes) if stripes else None
        extra = {}
    base = fwi_session_factory(
        cfg, time_model, stripes_for=stripes_for,
        exchange_interval=K, scan_block=SCAN_BLOCK,
    )
    sessions = []

    def factory(res, start_step, restored):
        s = base(res, start_step, restored)
        sessions.append(s)
        return s

    rec = orch.run(
        session_factory=factory,
        initial=Resources(pods=[PodSpec(chips=64, slowdown=1.0,
                                        name="cluster")], shares=[1.0]),
        steps_total=steps, **extra,
    )
    check(sessions[-1].t == steps,
          f"session ended at step {sessions[-1].t}, not {steps}")
    return rec, sessions


def stripe_devices(session) -> list:
    return [sh.device for sh in session.carry[0].addressable_shards]


def rel_diff(a: np.ndarray, ref: np.ndarray) -> float:
    return float(np.max(np.abs(a - ref)) / np.max(np.abs(ref)))


def fwi_phase(name: str, cfg, *, stream_expected: bool, steps: int) -> None:
    """``steps`` must carry the wavefront across at least one strip
    seam of the kernel at full amplitude (about 0.15 rows per step near
    the surface), so the comparison sees the trapezoid's stitching."""
    from repro.fwi.solver import run_forward
    from repro.kernels.stencil.ops import (
        pick_bz_block, pick_bz_stream, pick_shot_tile, should_stream,
    )

    stream = should_stream(cfg.nz, cfg.nx, K)
    tile = pick_shot_tile(cfg.n_shots, cfg.nz, cfg.nx, K, stream=stream)
    bz = (pick_bz_stream(cfg.nz, cfg.nx, K, s=tile) if stream
          else pick_bz_block(cfg.nz, K))
    log(name, f"grid {cfg.nz}x{cfg.nx} shots={cfg.n_shots} k={K} "
              f"kernel={'streamed' if stream else 'resident'} "
              f"shot_tile={tile} bz={bz}")
    check(stream == stream_expected,
          f"{name}: should_stream picked "
          f"{'streamed' if stream else 'resident'}")

    c0 = _compile_s[0]
    t0 = time.perf_counter()
    rec, sessions = orchestrated(cfg, steps)
    wall = time.perf_counter() - t0
    compile_s = _compile_s[0] - c0
    check(not any(e.kind in ("burst", "scale") for e in rec.events),
          f"{name}: the run scaled ({[e.kind for e in rec.events]})")
    s = sessions[-1]
    # the first dispatch compiles; the later ones are the steady state
    steady = rec.step_times[s.block:]
    s_per_step = sum(steady) / len(steady)
    log(name, f"{steps} steps in {len(rec.step_times) // s.block} "
              f"dispatches of {s.block} on {len(stripe_devices(s))} "
              f"stripe(s); wall {wall!r} s")
    log(name, f"compile_s={compile_s!r}")
    log(name, f"measured_s_per_step={s_per_step!r} "
              f"(first dispatch, compile included: "
              f"{rec.step_times[0]!r} s/step)")
    text = s.runner.lower(*s.carry, s.t, s.block // s.k) \
        .compile().as_text()
    has_kernel = "tpu_custom_call" in text
    log(name, f"tpu_custom_call={has_kernel} "
              f"(x{text.count('tpu_custom_call')})")
    check(has_kernel, f"{name}: the compiled runner holds no Pallas kernel")
    log(name, f"peak_bytes_in_use={peak_bytes()}")

    p = np.asarray(s.p)
    del rec, sessions, s
    gc.collect()
    ref, _ = run_forward(cfg, use_pallas=False, steps=steps, k=K)
    err = rel_diff(p, np.asarray(ref.p))
    log(name, f"max|p - p_ref| / max|p_ref| = {err!r} over {steps} steps "
              f"(pure-jnp reference on the chip; limit {TOL})")
    check(err <= TOL, f"{name}: differs from the reference by {err}")
    del ref
    gc.collect()


def one_stripe(cfg, steps: int) -> np.ndarray:
    """The job on one stripe: what the striped and elastic runs are
    compared with."""
    _, sessions = orchestrated(cfg, steps, stripes=1)
    log("fwi_4chip", f"1 stripe: {steps} steps on "
                     f"{[str(d) for d in stripe_devices(sessions[-1])]}")
    return np.asarray(sessions[-1].p)


def four_chip_phase(cfg, steps: int = 100 * SCAN_BLOCK,
                    elastic_steps: int = 15 * SCAN_BLOCK) -> None:
    """``steps`` is long enough for the 4096² wavefronts to reach the
    nearest stripe seam (41 columns from a source) at full amplitude,
    so a broken halo exchange would show; ``elastic_steps`` is the
    deadline-squeeze scenario's length."""
    name = "fwi_4chip"
    one = one_stripe(cfg, steps)
    gc.collect()

    c0 = _compile_s[0]
    rec, sessions = orchestrated(cfg, steps, stripes=4)
    s = sessions[-1]
    devs = stripe_devices(s)
    widths = [sh.data.shape[-1] for sh in s.carry[0].addressable_shards]
    steady = rec.step_times[s.block:]
    log(name, f"4 stripes: devices {[str(d) for d in devs]}, "
              f"columns {widths}; compile_s={_compile_s[0] - c0!r} "
              f"measured_s_per_step={sum(steady) / len(steady)!r}")
    check(len(set(devs)) == 4, f"stripes share devices: {devs}")
    check(widths == [cfg.nx // 4] * 4, f"stripe widths {widths}")
    err = rel_diff(np.asarray(s.p), one)
    log(name, f"4 stripes vs 1 stripe: max|dp| / max|p| = {err!r} "
              f"(limit {TOL})")
    check(err <= TOL, f"4 stripes differ from 1 stripe by {err}")
    del rec, sessions, s, one
    gc.collect()

    one = one_stripe(cfg, elastic_steps)
    rec, sessions = orchestrated(cfg, elastic_steps, elastic=True)
    kinds = [e.detail["kind"] for e in rec.events if e.kind == "scale"]
    stripes = [len(stripe_devices(x)) for x in sessions]
    grown = [x for x in sessions if len(stripe_devices(x)) == 4]
    log(name, f"elastic: scale events {kinds}, stripes per session "
              f"{stripes}, met_deadline={rec.met_deadline} "
              f"(elapsed {rec.elapsed_s!r} of {rec.deadline_s!r} s, "
              f"modeled)")
    check("grow" in kinds, f"elastic run never grew: {kinds}")
    check("retire" in kinds, f"elastic run never retired: {kinds}")
    check(grown and len(set(stripe_devices(grown[0]))) == 4,
          "the grown session does not span 4 devices")
    check(stripes[-1] == 1, f"ended on {stripes[-1]} stripes")
    err = rel_diff(np.asarray(sessions[-1].p), one)
    log(name, f"elastic vs unscaled: max|dp| / max|p| = {err!r} "
              f"(limit {TOL})")
    check(err <= TOL, f"elastic run differs from the unscaled by {err}")
    log(name, f"peak_bytes_in_use={peak_bytes()}")


# ------------------------------------------------------------------- LM


def lm_phase(argv: list[str]) -> None:
    from repro.launch import train

    out = train.main(argv + ["--steps", "3", "--log-every", "1"])
    losses, step_s = out["loss"], out["step_s"]
    log("lm_train", f"loss={losses!r}")
    log("lm_train", f"step_s={step_s!r} (the first includes compilation)")
    log("lm_train", f"peak_bytes_in_use={peak_bytes()}")
    check(len(losses) == 3 and all(math.isfinite(x) for x in losses),
          f"lm_train: losses {losses}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devs = tpu_devices(args.chips)

    import jax

    from repro.fwi.solver import FWIConfig
    from repro.launch.compile_cache import enable_compilation_cache

    cache = Path(enable_compilation_cache())
    entries = len(list(cache.glob("*-cache"))) if cache.is_dir() else 0
    log("setup", f"compilation cache: {cache} ({entries} entries at start)")
    jax.monitoring.register_event_duration_secs_listener(_count_compile)

    production = FWIConfig(nz=4096, nx=4096, n_shots=16)
    if args.chips == 4:
        four_chip_phase(production)
    else:
        # the resident strips are 120 rows tall, the streamed ones 16
        fwi_phase("fwi_resident", FWIConfig(nz=600, nx=600, n_shots=4),
                  stream_expected=False, steps=200 * SCAN_BLOCK)
        fwi_phase("fwi_streamed", production, stream_expected=True,
                  steps=50 * SCAN_BLOCK)
        lm_phase(["--arch", "mamba2-370m", "--seq", "1024", "--batch", "8"])
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
