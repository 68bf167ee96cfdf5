"""Train-step builders: microbatched grad accumulation, sharded optimizer
update, optional two-level compressed cross-pod reduction.

Two builders:
  * build_train_step       — pure-SPMD baseline (XLA schedules all
                             collectives, incl. the (pod,data) grad
                             all-reduce).
  * build_compressed_train_step — shard_map manual over "pod": gradients
                             reduce over "data" automatically (ICI), then
                             cross the pod boundary as int8 (DCI) via
                             optim/compression.cross_pod_reduce.  This is
                             the TPU rendering of the paper's cluster<->
                             cloud synchronization step (Fig.1 step 8)
                             plus the beyond-paper bandwidth optimization.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, RunConfig
from repro.models import model as M
from repro.optim import Optimizer
from repro.optim.compression import cross_pod_reduce
from repro.sharding.rules import (
    AxisRules,
    abstract_params,
    axis_rules,
    init_params,
    param_shardings,
    zero1_shardings,
)

# ---------------------------------------------------------------------------
# State schema / shardings
# ---------------------------------------------------------------------------


def state_schema(cfg: ModelConfig, run: RunConfig, optimizer: Optimizer):
    psch = M.schema(cfg)
    from repro.sharding.rules import ParamSpec

    return {
        "params": psch,
        "opt": optimizer.state_schema(psch),
        "step": ParamSpec((), (), jnp.int32, lambda k, s, d: jnp.zeros(s, d)),
    }


def state_shardings(sch, rules: AxisRules, run: RunConfig):
    out = {
        "params": param_shardings(sch["params"], rules),
        "step": rules.sharding((), ()),
    }
    shard_fn = zero1_shardings if run.zero1 else param_shardings
    out["opt"] = shard_fn(sch["opt"], rules)
    return out


def init_state(sch, key):
    params = init_params(sch["params"], key)
    return params  # opt state initialized by optimizer.init (runtime)


def batch_pspecs(batch_specs: dict, rules: AxisRules):
    """PartitionSpecs for a train/serve input dict (batch-dim sharded)."""
    out = {}
    for k, v in batch_specs.items():
        if v.shape == ():
            out[k] = P()
        else:
            out[k] = rules.spec(("batch",) + (None,) * (len(v.shape) - 1),
                                v.shape)
    return out


def batch_shardings(batch_specs: dict, rules: AxisRules):
    from jax.sharding import NamedSharding

    return {
        k: NamedSharding(rules.mesh, s)
        for k, s in batch_pspecs(batch_specs, rules).items()
    }


# ---------------------------------------------------------------------------
# Gradient computation (shared)
# ---------------------------------------------------------------------------


def _loss_of(cfg: ModelConfig, run: RunConfig):
    def f(params, mb):
        return M.loss_fn(
            cfg, params, mb, loss_chunk=run.loss_chunk, remat=run.remat,
        )

    return f


def compute_grads(cfg: ModelConfig, run: RunConfig, params, batch,
                  grad_pspecs=None):
    """Returns (grads, metrics).  Microbatched when run.microbatch is set
    and smaller than the global batch.

    Gradients are sharding-constrained to the parameter layout per
    microbatch: with FSDP params this turns the per-µbatch gradient
    all-reduce into a reduce-scatter (ZeRO-2 style) — without the
    constraint XLA keeps grads replicated over "data" and all-reduces
    full parameter volume every accumulation step.
    """
    loss_of = _loss_of(cfg, run)
    B = batch["tokens"].shape[0]
    mb_size = run.microbatch or B

    def constrain(g):
        if grad_pspecs is None:
            return g
        return jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(x, s),
            g, grad_pspecs,
        )

    if mb_size >= B:
        (loss, metrics), grads = jax.value_and_grad(loss_of, has_aux=True)(
            params, batch
        )
        return constrain(grads), metrics

    assert B % mb_size == 0, (B, mb_size)
    n_acc = B // mb_size
    gdtype = jnp.dtype(run.grad_dtype)
    mbs = jax.tree.map(
        lambda x: x.reshape(n_acc, mb_size, *x.shape[1:]), batch
    )
    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, gdtype), params)

    def body(carry, mb):
        gacc, lacc, nll, cnt = carry
        (loss, metrics), g = jax.value_and_grad(loss_of, has_aux=True)(
            params, mb
        )
        g = constrain(g)
        gacc = jax.tree.map(lambda a, b: a + b.astype(gdtype), gacc, g)
        return (
            gacc,
            lacc + loss,
            nll + metrics["nll_sum"],
            cnt + metrics["token_count"],
        ), None

    (gsum, lsum, nll, cnt), _ = jax.lax.scan(
        body, (g0, 0.0, 0.0, 0.0), mbs
    )
    # keep grads in the accumulation dtype — optimizers upcast per-leaf
    # (chunked over stacked layers); a blanket f32 cast here doubles the
    # live gradient footprint for the ≥200B models
    grads = jax.tree.map(lambda g: g / n_acc, gsum)
    metrics = {"loss": lsum / n_acc, "nll_sum": nll, "token_count": cnt}
    return grads, metrics


# ---------------------------------------------------------------------------
# Baseline SPMD train step
# ---------------------------------------------------------------------------


def build_train_step(
    cfg: ModelConfig, run: RunConfig, optimizer: Optimizer,
    rules: AxisRules | None = None,
):
    grad_pspecs = None
    if rules is not None:
        from jax.sharding import NamedSharding

        from repro.sharding.rules import param_pspecs

        grad_pspecs = jax.tree.map(
            lambda s: NamedSharding(rules.mesh, s),
            param_pspecs(M.schema(cfg), rules),
        )

    def step(state, batch):
        with axis_rules(rules):
            grads, metrics = compute_grads(
                cfg, run, state["params"], batch, grad_pspecs
            )
            new_params, new_opt = optimizer.update(
                grads, state["opt"], state["params"], state["step"]
            )
        return (
            {"params": new_params, "opt": new_opt, "step": state["step"] + 1},
            metrics,
        )

    return step


# ---------------------------------------------------------------------------
# Compressed cross-pod train step (manual over "pod")
# ---------------------------------------------------------------------------


def build_compressed_train_step(
    cfg: ModelConfig, run: RunConfig, optimizer: Optimizer, rules: AxisRules,
):
    """Requires a mesh with a 'pod' axis.  Gradients cross the pod boundary
    as int8; everything else stays automatically sharded (data/model)."""
    mesh = rules.mesh
    assert "pod" in mesh.shape, "compressed step needs a 'pod' mesh axis"
    npods = mesh.shape["pod"]
    # inside the manual-pod region, batch shards over data only
    inner_rules = dataclasses.replace(
        rules,
        rules={**rules.rules, "batch": (("data",),)},
    )

    def inner(state, batch):
        with axis_rules(inner_rules):
            grads, metrics = compute_grads(cfg, run, state["params"], batch)
            # each pod's grads are normalized by ITS token count; the
            # global gradient is the token-weighted mean across pods
            cnt = metrics["token_count"].astype(jnp.float32)
            grads = jax.tree.map(lambda g: g * cnt, grads)
            grads = cross_pod_reduce(
                grads, "pod", method=run.gradient_compression
            )
            cnt_total = jax.lax.psum(cnt, "pod")
            grads = jax.tree.map(lambda g: g / cnt_total, grads)
            metrics = jax.tree.map(
                lambda m: jax.lax.pmean(m, "pod"), metrics
            )
            new_params, new_opt = optimizer.update(
                grads, state["opt"], state["params"], state["step"]
            )
        return (
            {"params": new_params, "opt": new_opt, "step": state["step"] + 1},
            metrics,
        )

    def step(state, batch):
        state_specs = jax.tree.map(lambda _: P(), state)
        batch_specs = jax.tree.map(
            lambda x: P("pod") if x.ndim else P(), batch
        )
        return jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(state_specs, batch_specs),
            out_specs=(state_specs, P()),
            axis_names={"pod"},
            check_vma=False,
        )(state, batch)

    return step
