"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --smoke \
        --steps 50 --deadline 120

Runs the full substrate: config -> model -> sharded train step ->
synthetic pipeline -> optimizer, with step-time monitoring, deadline
prediction (the paper's loop), periodic async checkpointing and
auto-resume.  --smoke shrinks the arch for CPU; without it the full
config is used (TPU-scale — on CPU use the dry-run instead).

The *elastic* path (actual mid-run re-meshing) needs >1 device; see
examples/elastic_burst_demo.py which launches with 8 host devices.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.configs import RunConfig, get_config, smoke_config
from repro.configs.shapes import ShapeConfig
from repro.core import DeadlinePredictor, StepTimeMonitor
from repro.data.pipeline import SyntheticLMPipeline
from repro.launch.mesh import make_host_mesh
from repro.optim import make_optimizer, warmup_cosine
from repro.runtime import train_step as ts
from repro.sharding.rules import abstract_params, init_params, make_rules


def build_session(cfg, run, mesh, steps_total):
    rules = make_rules(mesh, "train")
    opt = make_optimizer(
        run.optimizer or cfg.optimizer,
        warmup_cosine(total_steps=steps_total),
    )
    sch = ts.state_schema(cfg, run, opt)
    shardings = ts.state_shardings(sch, rules, run)
    step_fn = jax.jit(
        ts.build_train_step(cfg, run, opt, rules), donate_argnums=(0,)
    )
    return opt, sch, shardings, step_fn, rules


def main(argv=None) -> dict:
    """Train; returns ``{"loss": [...], "step_s": [...]}`` for the steps
    this call ran (the first step's time includes compilation)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--deadline", type=float, default=None,
                    help="seconds; enables the monitoring/prediction loop")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    shape = ShapeConfig("cli", "train", args.seq, args.batch)
    run = RunConfig(microbatch=args.microbatch, loss_chunk=min(512, args.seq))
    mesh = make_host_mesh()
    opt, sch, shardings, step_fn, rules = build_session(
        cfg, run, mesh, args.steps
    )

    pipeline = SyntheticLMPipeline(cfg, shape)
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start_step = 0
    if args.resume and mgr and mgr.latest_step() is not None:
        abstract = abstract_params(sch)
        state, extra = mgr.restore(abstract, shardings=shardings)
        pipeline.restore(extra)
        start_step = int(extra.get("data_step", 0))
        print(f"[train] resumed from step {start_step}")
    else:
        params = init_params(sch["params"], jax.random.key(0))
        params = jax.device_put(params, shardings["params"])
        state = {
            "params": params,
            "opt": jax.jit(opt.init, out_shardings=shardings["opt"])(params),
            # placed like the step's output, or step 2 recompiles
            "step": jax.device_put(jnp.zeros((), jnp.int32),
                                   shardings["step"]),
        }

    monitor = StepTimeMonitor()
    predictor = (
        DeadlinePredictor(args.deadline) if args.deadline else None
    )
    t_start = time.monotonic()
    losses, step_s = [], []
    for step in range(start_step, args.steps):
        batch = pipeline.batch_at(step)
        t0 = time.monotonic()
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])  # blocks
        dt = time.monotonic() - t0
        losses.append(loss)
        step_s.append(dt)
        monitor.observe(dt)
        pipeline.state.step = step + 1
        if mgr and (step + 1) % args.ckpt_every == 0:
            mgr.save(step + 1, state, extra=pipeline.state.to_extra())
        if (step + 1) % args.log_every == 0 or step == start_step:
            msg = (f"[train] step {step + 1}/{args.steps} "
                   f"loss={loss:.4f} {dt*1000:.0f}ms")
            if predictor:
                est = predictor.estimate(
                    monitor, step + 1, args.steps,
                    time.monotonic() - t_start,
                )
                msg += (f" est_total={est.estimated_total_s:.0f}s "
                        f"slack={est.slack_s:+.0f}s"
                        + (" [DEADLINE AT RISK — would burst]"
                           if est.will_miss else ""))
            print(msg, flush=True)
    if mgr:
        mgr.save(args.steps, state, extra=pipeline.state.to_extra(),
                 wait=True)
    print(f"[train] done in {time.monotonic() - t_start:.1f}s")
    return {"loss": losses, "step_s": step_s}


if __name__ == "__main__":
    main()
