"""Mixture-of-Experts with capacity-based top-k routing.

Baseline dispatch is the t5x/flaxformer einsum formulation — robust under
pjit (experts shard on "model", token groups on "data") — wrapped in a
lax.scan over token groups so the (tokens, E, C) dispatch tensors stay
bounded regardless of sequence length.  A sort/scatter-based dispatch
(dispatch="scatter") removes the one-hot einsum FLOPs and is the
documented hillclimb for the compute-bound MoE cells (EXPERIMENTS.md
§Perf); see apply_moe_scatter.

Routing: softmax router (fp32), top-k with normalized gates, per-group
expert capacity C = ceil(T·k·cf / E) rounded to a multiple of 4.
Aux losses: switch load-balance + router z-loss.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.sharding.rules import current_rules, normal_param, param, shard

# ---------------------------------------------------------------------------
# Schema
# ---------------------------------------------------------------------------


def moe_schema(cfg: ModelConfig):
    m = cfg.moe
    d, f, E = cfg.d_model, m.d_ff, m.num_experts
    # EP layout: weights shard E over "data" (+ TP on f over "model");
    # the embed dim must NOT be FSDP-sharded (its all-gather per use is
    # exactly what EP removes) — axes pick that automatically since
    # "data" is taken by experts_ep.
    if m.ep_over_dp:
        # EP layout: E → "data", d → "model" (the all-to-all payload is
        # d-sliced), f replicated.  256-way sharded, never re-gathered.
        s = {
            "router": normal_param((d, E), ("embed", "experts"), 0.02,
                                   jnp.float32),
            "w_gate": param((E, d, f), ("experts_ep", "ep_embed", None),
                            cfg.pdtype),
            "w_up": param((E, d, f), ("experts_ep", "ep_embed", None),
                          cfg.pdtype),
            "w_down": param((E, f, d), ("experts_ep", None, "ep_embed"),
                            cfg.pdtype),
        }
    else:
        s = {
            "router": normal_param((d, E), ("embed", "experts"), 0.02,
                                   jnp.float32),
            "w_gate": param((E, d, f), ("experts", "embed", "mlp"),
                            cfg.pdtype),
            "w_up": param((E, d, f), ("experts", "embed", "mlp"),
                          cfg.pdtype),
            "w_down": param((E, f, d), ("experts", "mlp", "embed"),
                            cfg.pdtype),
        }
    if m.num_shared_experts:
        fs = m.num_shared_experts * f
        s["shared"] = {
            "gate": param((d, fs), ("embed", "mlp"), cfg.pdtype),
            "up": param((d, fs), ("embed", "mlp"), cfg.pdtype),
            "down": param((fs, d), ("mlp", "embed"), cfg.pdtype),
        }
    return s


def expert_capacity(tokens_per_group: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    c = int(tokens_per_group * m.top_k * m.capacity_factor / m.num_experts)
    c = max(4, c)
    return (c + 3) // 4 * 4


def _dp_size() -> int:
    rules = current_rules()
    if rules is None:
        return 1
    return rules.mesh_axis_size(("pod", "data"))


# ---------------------------------------------------------------------------
# Routing (shared by both dispatch paths)
# ---------------------------------------------------------------------------


def route(cfg: ModelConfig, p, x_f32: jax.Array):
    """x (..., T, d) fp32 -> (gate (...,T,k), idx (...,T,k), aux terms)."""
    m = cfg.moe
    logits = x_f32 @ p["router"].astype(jnp.float32)          # (...,T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, m.top_k)                 # (...,T,k)
    gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)
    # aux losses
    mask = jax.nn.one_hot(idx, m.num_experts, dtype=jnp.float32)  # (...,T,k,E)
    f_e = jnp.mean(jnp.sum(mask, axis=-2), axis=-2)           # (...,E) routed frac*k
    p_e = jnp.mean(probs, axis=-2)                            # (...,E)
    lb = m.num_experts * jnp.mean(jnp.sum(f_e / m.top_k * p_e, axis=-1))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return gate, idx, mask, lb, z


def _positions_in_expert(mask: jax.Array) -> jax.Array:
    """mask (..., T, k, E) one-hot -> position of each (t,k) within its
    expert queue, token-major priority.  Returns (..., T, k)."""
    shp = mask.shape
    T, K, E = shp[-3], shp[-2], shp[-1]
    flat = mask.reshape(*shp[:-3], T * K, E)
    pos_e = jnp.cumsum(flat, axis=-2) - flat                  # count before
    pos = jnp.sum(pos_e * flat, axis=-1)                      # (..., T*K)
    return pos.reshape(*shp[:-3], T, K)


# ---------------------------------------------------------------------------
# Einsum (t5x-style) dispatch — baseline
# ---------------------------------------------------------------------------


def _moe_group_einsum(cfg: ModelConfig, p, x_g: jax.Array, C: int):
    """x_g (G, T, d) -> (y (G, T, d), lb, z).  G is data-sharded.

    ep_over_dp=False (baseline): experts shard on "model" only; with FSDP
    ("embed"→data) the expert weights are re-gathered over "data" at every
    use — the dominant collective in the MoE train baselines.

    ep_over_dp=True (hillclimb A): the dispatched token tensor is
    resharded with experts over ("data","model") — an all-to-all — and
    the expert weights stay fully sharded: no weight gathers, and expert
    weight grads are complete locally (every token using expert e visits
    its owner), so they need no cross-device reduction either.
    """
    m = cfg.moe
    dt = cfg.cdtype
    gate, idx, mask, lb, z = route(cfg, p, x_g.astype(jnp.float32))
    pos = _positions_in_expert(mask)                          # (G,T,k)
    keep = (pos < C).astype(jnp.float32)
    pos_oh = jax.nn.one_hot(pos, C, dtype=jnp.float32) * keep[..., None]
    dispatch = jnp.einsum("gtke,gtkc->gtec", mask, pos_oh).astype(dt)
    combine = jnp.einsum(
        "gtke,gtkc,gtk->gtec", mask, pos_oh, gate
    ).astype(dt)
    dispatch = shard(dispatch, "batch", None, "experts", None)
    combine = shard(combine, "batch", None, "experts", None)
    xe = jnp.einsum("gtd,gtec->gecd", x_g.astype(dt), dispatch)
    xe = shard(xe, "batch", "experts", None, None)
    h = jax.nn.silu(
        jnp.einsum("gecd,edf->gecf", xe, p["w_gate"].astype(dt))
    ) * jnp.einsum("gecd,edf->gecf", xe, p["w_up"].astype(dt))
    ye = jnp.einsum("gecf,efd->gecd", h, p["w_down"].astype(dt))
    ye = shard(ye, "batch", "experts", None, None)
    y = jnp.einsum("gecd,gtec->gtd", ye, combine)
    y = shard(y, "batch", None, None)
    return y, lb, z


# ---------------------------------------------------------------------------
# Sort/scatter dispatch — FLOP-free routing (hillclimb path)
# ---------------------------------------------------------------------------


def _moe_group_scatter(cfg: ModelConfig, p, x_g: jax.Array, C: int):
    """Same contract as _moe_group_einsum but routes by sort+gather/scatter:
    no (T,E,C) one-hot matmuls, so HLO FLOPs ≈ useful expert FLOPs."""
    m = cfg.moe
    dt = cfg.cdtype
    G, T, d = x_g.shape
    E, K = m.num_experts, m.top_k
    gate, idx, mask, lb, z = route(cfg, p, x_g.astype(jnp.float32))
    pos = _positions_in_expert(mask)                          # (G,T,K)
    keep = pos < C

    def per_group(xg, idxg, gateg, posg, keepg):
        # xg (T,d); idxg/gateg/posg/keepg (T,K)
        slot = jnp.where(keepg, idxg * C + posg, E * C)       # (T,K)
        slot_f = slot.reshape(T * K).astype(jnp.int32)
        src = jnp.repeat(jnp.arange(T), K)
        buf = jnp.zeros((E * C + 1, d), dt)
        buf = buf.at[slot_f].set(xg.astype(dt)[src], mode="drop",
                                 unique_indices=False)
        xe = buf[: E * C].reshape(E, C, d)
        h = jax.nn.silu(
            jnp.einsum("ecd,edf->ecf", xe, p["w_gate"].astype(dt))
        ) * jnp.einsum("ecd,edf->ecf", xe, p["w_up"].astype(dt))
        ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dt))
        ye_f = ye.reshape(E * C, d)
        gath = jnp.take(ye_f, jnp.clip(slot_f, 0, E * C - 1), axis=0)
        gath = gath * (keepg.reshape(T * K, 1)).astype(dt)
        w = gateg.reshape(T * K, 1).astype(dt)
        y = jnp.zeros((T, d), dt).at[src].add(gath * w)
        return y

    y = jax.vmap(per_group)(x_g, idx, gate, pos, keep)
    return y, lb, z


_GROUP_FNS = {"einsum": _moe_group_einsum, "scatter": _moe_group_scatter}


# ---------------------------------------------------------------------------
# Expert-parallel path (explicit shard_map; hillclimb A)
# ---------------------------------------------------------------------------


def apply_moe_ep(cfg: ModelConfig, p, x: jax.Array):
    """EP over "data" with TP over "model", fully manual collectives.

    Per (data, model) rank: route locally (scatter dispatch — no one-hot
    einsum FLOPs), all_to_all the d-SLICED token payload to expert
    owners, expert matmuls with E→data / d→model weights (psum over
    "model" before the nonlinearity), d-sliced payload back via the
    reverse all_to_all, per-token combine, one small all-gather of the
    output d-slices.  Wire per layer ≈ slots·d/tp·2 (a2a) + slots·f
    (psum) + tokens·d (AG) — vs. the baseline's re-gather of the full
    expert bank over "data" every use.
    """
    from jax.sharding import PartitionSpec as P

    from repro.sharding.rules import current_rules

    m = cfg.moe
    rules = current_rules()
    mesh = rules.mesh if rules is not None else None
    if mesh is None or "data" not in mesh.shape \
            or m.num_experts % mesh.shape["data"]:
        # no mesh context (CPU smoke) or indivisible: einsum fallback
        return _apply_moe_grouped(cfg, p, x)
    dp = mesh.shape["data"]
    tp = mesh.shape.get("model", 1)
    pods = mesh.shape.get("pod", 1)
    E, K = m.num_experts, m.top_k
    B, S, d = x.shape
    N = B * S
    dt = cfg.cdtype
    if N % (dp * pods) or d % tp:
        return _apply_moe_grouped(cfg, p, x)
    # manual over pod too (XLA's partitioner crashes on this region with
    # an auto pod axis); EP stays INTRA-pod — the slow DCI link never
    # carries dispatch traffic, matching the paper's placement principle
    Tl = N // (dp * pods)
    C = expert_capacity(Tl, cfg)
    dl = d // tp
    El = E // dp
    batch_axes = ("pod", "data") if pods > 1 else ("data",)

    def body(xl, router, wg, wu, wd):
        # xl (Tl, d); wg/wu (El, dl, f); wd (El, f, dl)
        gate, idx, mask, lb, z = route(
            cfg, {"router": router}, xl.astype(jnp.float32)
        )
        pos = _positions_in_expert(mask)                     # (Tl, K)
        keep = pos < C
        slot = jnp.where(keep, idx * C + pos, E * C)
        slot_f = slot.reshape(Tl * K).astype(jnp.int32)
        src = jnp.repeat(jnp.arange(Tl), K)
        j = jax.lax.axis_index("model")
        xsl = jax.lax.dynamic_slice_in_dim(
            xl.astype(dt), j * dl, dl, axis=1
        )                                                     # (Tl, dl)
        buf = jnp.zeros((E * C + 1, dl), dt)
        buf = buf.at[slot_f].set(xsl[src], mode="drop")[: E * C]
        buf = buf.reshape(E, C, dl)
        # token-major -> expert-major over the SAME shards
        xe = jax.lax.all_to_all(
            buf, "data", split_axis=0, concat_axis=1, tiled=True
        )                                                     # (El, dp*C, dl)
        # expert FFN: contraction dim d split over "model"
        hg = jax.lax.psum(
            jnp.einsum("ead,edf->eaf", xe, wg.astype(dt)), "model"
        )
        hu = jax.lax.psum(
            jnp.einsum("ead,edf->eaf", xe, wu.astype(dt)), "model"
        )
        h = jax.nn.silu(hg) * hu
        ye = jnp.einsum("eaf,efd->ead", h, wd.astype(dt))     # d-sliced out
        back = jax.lax.all_to_all(
            ye, "data", split_axis=1, concat_axis=0, tiled=True
        ).reshape(E * C, dl)                                  # my slots
        gath = jnp.take(back, jnp.clip(slot_f, 0, E * C - 1), axis=0)
        gath = gath * keep.reshape(Tl * K, 1).astype(dt)
        w = gate.reshape(Tl * K, 1).astype(dt)
        y_slice = jnp.zeros((Tl, dl), dt).at[src].add(gath * w)
        y = jax.lax.all_gather(y_slice, "model", axis=1, tiled=True)
        lb = jax.lax.pmean(lb, batch_axes)
        z = jax.lax.pmean(z, batch_axes)
        return y, lb, z

    y, lb, z = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(batch_axes, None),                   # tokens
            P(None, None),                         # router (replicated in)
            P("data", "model", None),              # w_gate
            P("data", "model", None),              # w_up
            P("data", None, "model"),              # w_down
        ),
        out_specs=(P(batch_axes, None), P(), P()),
        axis_names=set(batch_axes) | {"model"},
        check_vma=False,
    )(
        x.reshape(N, d), p["router"], p["w_gate"], p["w_up"], p["w_down"]
    )
    y = y.reshape(B, S, d)
    return y, lb, z


# ---------------------------------------------------------------------------
# Top-level MoE layer
# ---------------------------------------------------------------------------


def apply_moe(cfg: ModelConfig, p, x: jax.Array):
    """x (B, S, d) -> (y (B, S, d), {"lb_loss", "z_loss"})."""
    m = cfg.moe
    if m.ep_over_dp:
        y, lb, z = apply_moe_ep(cfg, p, x)
    else:
        y, lb, z = _apply_moe_grouped(cfg, p, x)

    if m.num_shared_experts:
        dt = cfg.cdtype
        sp = p["shared"]
        hs = jax.nn.silu(x.astype(dt) @ sp["gate"].astype(dt)) * (
            x.astype(dt) @ sp["up"].astype(dt)
        )
        hs = shard(hs, "batch", None, "mlp")
        y = y + hs @ sp["down"].astype(dt)

    aux = {
        "lb_loss": m.router_aux_weight * lb,
        "z_loss": m.router_z_weight * z,
    }
    return y, aux


def _apply_moe_grouped(cfg: ModelConfig, p, x: jax.Array):
    m = cfg.moe
    B, S, d = x.shape
    N = B * S
    dp = _dp_size()
    xf = x.reshape(N, d)
    group_fn = _GROUP_FNS[m.dispatch]

    if N % dp or (N // dp) < 4:
        dp_g = 1
    else:
        dp_g = dp
    per_shard = N // dp_g
    g_eff = min(m.group_size, per_shard)
    n_iter = per_shard // g_eff
    if per_shard % g_eff:
        n_iter, g_eff = 1, per_shard
    C = expert_capacity(g_eff, cfg)

    # (N, d) -> (dp_g, n_iter, g_eff, d): shard-local contiguous rows
    xg = xf.reshape(dp_g, n_iter, g_eff, d)
    xg = shard(xg, "batch", None, None, None)

    if n_iter == 1:
        y, lb, z = group_fn(cfg, p, xg[:, 0], C)
        y = y[:, None]
    else:
        xs = jnp.moveaxis(xg, 1, 0)  # (n_iter, dp_g, g_eff, d)
        xs = shard(xs, None, "batch", None, None)

        def body(acc, x_it):
            y_it, lb_it, z_it = group_fn(cfg, p, x_it, C)
            return (acc[0] + lb_it, acc[1] + z_it), y_it

        (lb, z), ys = jax.lax.scan(body, (0.0, 0.0), xs)
        lb, z = lb / n_iter, z / n_iter
        y = jnp.moveaxis(ys, 0, 1)   # (dp_g, n_iter, g_eff, d)

    y = y.reshape(B, S, d)
    y = shard(y, "batch", None, "d_model")
    return y, lb, z
