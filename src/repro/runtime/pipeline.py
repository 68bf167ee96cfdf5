"""Pipeline parallelism over the "pod" axis (GPipe, shard_map manual).

The TPU-native rendering of the paper's *slow cluster<->cloud link*
insight, as an alternative to cross-pod data parallelism: with DP the
entire gradient volume crosses DCI every step (granite-8b: ~1.3 GB/dev);
with 2-stage PP only the stage-boundary activations cross, between
matched device pairs (~66 MB/dev for the same cell) — each pod owns half
the layers, so layer-weight gradients never leave their pod.

Mechanics: shard_map manual over {"pod"} with data/model auto inside.
The stacked-layers dim of every block parameter is sharded P("pod") —
each pod holds its contiguous layer slice.  A lax.scan over
n_micro + stages - 1 ticks runs the GPipe fill/drain schedule; the
activation moves stage-to-stage via ppermute each tick.  jax.grad
through the tick scan IS the GPipe backward (ppermute transposes to the
reverse permute).  Embedding/unembedding params are replicated across
pods; their (stage-local) gradients are psum'd over "pod".

Restrictions (asserted): a single homogeneous BlockDef whose repeat
divides by the stage count; no MoE/cross-attention/MTP (their own
shard_map regions do not nest under a manual pod axis) — i.e. the dense
LM family, which is exactly where cross-pod DP vs PP is the interesting
trade.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import BlockDef, ModelConfig, RunConfig
from repro.models import model as M
from repro.models.layers import apply_norm, embed_tokens
from repro.models.transformer import apply_block_full
from repro.optim import Optimizer
from repro.sharding.rules import (
    AxisRules,
    axis_rules,
    is_spec,
    mesh_and_manual,
    shard,
)


def pipeline_compatible(cfg: ModelConfig) -> bool:
    return (
        len(cfg.blocks) == 1
        and all(m == "attn" and mlp == "dense"
                for m, mlp in cfg.blocks[0].pattern)
        and not cfg.cross_attention
        and not cfg.mtp
        and cfg.moe is None
    )


def _block_param_specs(schema) -> Any:
    """P('pod') on the stacked-layers dim for block params, P() otherwise."""

    def spec_of(path, leaf):
        keys = [getattr(p, "key", None) for p in path]
        if any(isinstance(k, str) and k.startswith("b") and k[1:].isdigit()
               for k in keys):
            return P("pod")
        return P()

    flat, treedef = jax.tree_util.tree_flatten_with_path(
        schema, is_leaf=is_spec
    )
    return jax.tree_util.tree_unflatten(
        treedef, [spec_of(p, l) for p, l in flat]
    )


def build_pipeline_train_step(
    cfg: ModelConfig, run: RunConfig, optimizer: Optimizer,
    rules: AxisRules,
):
    """Returns (step_fn, state_in_specs) — step_fn(state, batch) with the
    state's block params stage-sharded over 'pod'."""
    assert pipeline_compatible(cfg), cfg.name
    mesh = rules.mesh
    stages = mesh.shape.get("pod", 1)
    assert stages > 1, "pipeline needs a 'pod' axis"
    bdef = cfg.blocks[0]
    assert bdef.repeat % stages == 0, (bdef.repeat, stages)
    n_micro = run.pp_microbatches
    local_bdef = BlockDef(pattern=bdef.pattern,
                          repeat=bdef.repeat // stages)
    # inside the manual pod region: batch shards over "data", and the
    # residual/boundary activation over "model" (SP) — the ppermute then
    # moves per-device shards only, which is the whole point of PP here
    inner_rules = dataclasses.replace(
        rules,
        rules={**rules.rules, "batch": (("data",),),
               "seq_res": (("model",),)},
    )
    fwd_perm = [(i, i + 1) for i in range(stages - 1)]

    def loss_fn(params, batch, sid):
        # manual over pod: params['b0'] holds THIS stage's layer slice;
        # sid arrives as data (a P("pod")-sharded arange) rather than
        # lax.axis_index — partition-id lowering is not portable across
        # partitioners
        tokens = batch["tokens"]                   # (B, S) pod-replicated
        mask = batch.get("loss_mask")
        if mask is None:
            mask = jnp.ones(tokens.shape, jnp.float32)
        B, S = tokens.shape
        assert B % n_micro == 0, (B, n_micro)
        mb = B // n_micro
        rope_cs = M.rope_full(cfg, S)
        labels = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
        lmask = jnp.pad(mask[:, 1:], ((0, 0), (0, 1)))

        def stage_compute(x, m_idx, active):
            """Run this pod's layers on x where active."""
            is_first = sid == 0
            tok_m = jax.lax.dynamic_slice_in_dim(tokens, m_idx * mb, mb, 0)
            x_in = jnp.where(
                is_first, embed_tokens(cfg, params, tok_m), x
            )
            y, _, aux = apply_block_full(
                cfg, local_bdef, params["b0"], x_in,
                rope_cs=rope_cs, causal=True, remat=cfg.remat,
            )
            y = jnp.where(active, y, x)
            return y, jnp.where(active, aux, 0.0)

        def last_stage_loss(x, m_idx, active):
            h = apply_norm(cfg, params["final_norm"], x)
            lab = jax.lax.dynamic_slice_in_dim(labels, m_idx * mb, mb, 0)
            lm = jax.lax.dynamic_slice_in_dim(lmask, m_idx * mb, mb, 0)
            lm = lm * active.astype(lm.dtype)
            nll, cnt = M.chunked_xent(cfg, params, h, lab, lm,
                                      run.loss_chunk)
            return nll, cnt

        def tick(carry, t):
            x_cur, nll, cnt, aux_acc = carry
            m_idx = jnp.clip(t - sid, 0, n_micro - 1)
            active = (t - sid >= 0) & (t - sid < n_micro)
            y, aux = stage_compute(x_cur, m_idx, active)
            is_last = sid == stages - 1
            nll_t, cnt_t = last_stage_loss(y, m_idx, active & is_last)
            take = (active & is_last).astype(jnp.float32)
            # hand my output to the next stage for the next tick; keep it
            # (data, model)-sharded so only per-device shards cross DCI
            y = shard(y, "batch", "seq_res", None)
            x_next = jax.lax.ppermute(y, "pod", fwd_perm)
            return (
                x_next, nll + nll_t * take, cnt + cnt_t * take,
                aux_acc + aux,
            ), None

        x0 = shard(
            jnp.zeros((mb, S, cfg.d_model), cfg.cdtype),
            "batch", "seq_res", None,
        )
        (x_last, nll, cnt, aux), _ = jax.lax.scan(
            tick, (x0, 0.0, 0.0, 0.0), jnp.arange(n_micro + stages - 1)
        )
        nll = jax.lax.psum(nll, "pod")
        cnt = jax.lax.psum(cnt, "pod")
        aux = jax.lax.psum(aux, "pod") / stages
        loss = nll / jnp.maximum(cnt, 1.0) + aux
        return loss, {"loss": loss, "nll_sum": nll, "token_count": cnt}

    def inner(state, batch, sid_arr):
        sid = sid_arr[0]
        with axis_rules(inner_rules):
            # within-pod FSDP/TP of the stage's weights: the manual pod
            # split leaves them replicated over (data, model) otherwise
            from jax.sharding import NamedSharding

            from repro.sharding.rules import param_pspecs

            pspecs = param_pspecs(M.schema(cfg), inner_rules)
            am, _ = mesh_and_manual()

            def constrain(x, spec):
                return jax.lax.with_sharding_constraint(
                    x, NamedSharding(am, spec)
                )

            state = dict(state)
            state["params"] = jax.tree.map(
                constrain, state["params"], pspecs
            )
            (loss, metrics), grads = jax.value_and_grad(
                lambda p, b: loss_fn(p, b, sid), has_aux=True
            )(state["params"], batch)
            # shared (pod-replicated) params: sum partial grads across
            # stages; stage-local layer grads stay local (the PP win)
            def psum_shared(path, g):
                keys = [getattr(p, "key", None) for p in path]
                if any(isinstance(k, str) and k.startswith("b")
                       and k[1:].isdigit() for k in keys):
                    return g
                return jax.lax.psum(g, "pod")

            flat, treedef = jax.tree_util.tree_flatten_with_path(grads)
            grads = jax.tree_util.tree_unflatten(
                treedef, [psum_shared(p, g) for p, g in flat]
            )
            new_params, new_opt = optimizer.update(
                grads, state["opt"], state["params"], state["step"]
            )
        return (
            {"params": new_params, "opt": new_opt,
             "step": state["step"] + 1},
            metrics,
        )

    psch = M.schema(cfg)
    param_specs = _block_param_specs(psch)
    opt_specs = _block_param_specs(optimizer.state_schema(psch))
    state_specs = {"params": param_specs, "opt": opt_specs, "step": P()}

    def step(state, batch):
        batch_specs = jax.tree.map(lambda _: P(), batch)
        sid_in = jnp.arange(stages, dtype=jnp.int32)
        return jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(state_specs, batch_specs, P("pod")),
            out_specs=(state_specs, P()),
            axis_names={"pod"},
            check_vma=False,
        )(state, batch, sid_in)

    return step, state_specs
