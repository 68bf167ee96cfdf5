"""Mamba-2 mixer via SSD (state-space duality), TPU-adapted.

Train/prefill use the chunked SSD algorithm (arXiv:2405.21060 §6): the
sequence is cut into chunks of Q tokens; within a chunk the recurrence is
computed in attention form (MXU-dense Q×Q matmuls), across chunks a cheap
lax.scan carries the (H, N, P) state.  Decode is the O(1) recurrent
update — this is why the ssm/hybrid archs run the long_500k cell.

Projections are split per stream (z/x/B/C/dt) instead of one fused
in_proj so each shards independently on "model" (d_inner 16-way); the
depthwise causal conv is expressed as width-4 shifted adds (channel-
sharded, no halo).  kernels/ssd holds the Pallas intra-chunk kernel for
real TPU; this module is the portable/sharded formulation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import rms_norm
from repro.sharding.rules import (
    ParamSpec,
    normal_param,
    param,
    scale_param,
    shard,
    zeros_param,
)


def _dims(cfg: ModelConfig):
    s = cfg.ssm
    d_in = s.d_inner(cfg.d_model)
    H = s.n_heads(cfg.d_model)
    return s, d_in, H, s.n_groups, s.d_state, s.head_dim


def mamba_schema(cfg: ModelConfig):
    s, d_in, H, G, N, P = _dims(cfg)
    d = cfg.d_model
    pd = cfg.pdtype

    def dt_bias_init(key, shape, dtype):
        # dt in [dt_min, dt_max] at init (inverse-softplus of uniform draw)
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(
            u * (jnp.log(s.dt_max) - jnp.log(s.dt_min)) + jnp.log(s.dt_min)
        )
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

    def a_log_init(key, shape, dtype):
        del key
        return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32)).astype(
            dtype
        )

    return {
        "wz": param((d, d_in), ("embed", "ssm_inner"), pd),
        "wx": param((d, d_in), ("embed", "ssm_inner"), pd),
        "wb": param((d, G * N), ("embed", None), pd),
        "wc": param((d, G * N), ("embed", None), pd),
        "wdt": param((d, H), ("embed", "ssm_heads"), pd),
        "conv_x": normal_param((s.d_conv, d_in), ("conv_w", "ssm_inner"), 0.1, pd),
        "conv_b": normal_param((s.d_conv, G * N), ("conv_w", None), 0.1, pd),
        "conv_c": normal_param((s.d_conv, G * N), ("conv_w", None), 0.1, pd),
        "conv_x_bias": zeros_param((d_in,), ("ssm_inner",), pd),
        "conv_b_bias": zeros_param((G * N,), (None,), pd),
        "conv_c_bias": zeros_param((G * N,), (None,), pd),
        "A_log": ParamSpec((H,), ("ssm_heads",), pd, a_log_init),
        "D": scale_param((H,), ("ssm_heads",), pd),
        "dt_bias": ParamSpec((H,), ("ssm_heads",), pd, dt_bias_init),
        "norm": scale_param((d_in,), ("ssm_inner",), pd),
        "out": param((d_in, d), ("ssm_inner", "embed"), pd),
    }


def mamba_cache_schema(cfg: ModelConfig, batch: int):
    s, d_in, H, G, N, P = _dims(cfg)
    cw = s.d_conv - 1
    return {
        "conv_x": zeros_param((batch, cw, d_in), ("batch", "conv_w", "ssm_inner"), cfg.cdtype),
        "conv_b": zeros_param((batch, cw, G * N), ("batch", "conv_w", None), cfg.cdtype),
        "conv_c": zeros_param((batch, cw, G * N), ("batch", "conv_w", None), cfg.cdtype),
        "state": zeros_param(
            (batch, H, N, P), ("batch", "ssm_heads", "ssm_state", None),
            jnp.float32,
        ),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv as shifted adds.  x (B,S,C), w (W,C)."""
    W = w.shape[0]
    out = x * w[-1]
    for i in range(1, W):
        shifted = jnp.pad(x[:, :-i], ((0, 0), (i, 0), (0, 0)))
        out = out + shifted * w[W - 1 - i]
    return out + b


def _conv_step(x_new: jax.Array, cache: jax.Array, w: jax.Array, b: jax.Array):
    """x_new (B,C); cache (B,W-1,C) previous raw inputs."""
    window = jnp.concatenate([cache, x_new[:, None]], axis=1)  # (B,W,C)
    y = jnp.einsum("bwc,wc->bc", window, w) + b
    return y, window[:, 1:]


def apply_mamba_full(
    cfg: ModelConfig,
    p,
    x: jax.Array,                 # (B, S, d)
    *,
    return_cache: bool = False,
):
    s, d_in, H, G, N, P = _dims(cfg)
    dt_c = cfg.cdtype
    B_, S, _ = x.shape
    x = x.astype(dt_c)
    z = x @ p["wz"].astype(dt_c)
    xs_raw = x @ p["wx"].astype(dt_c)
    b_raw = x @ p["wb"].astype(dt_c)
    c_raw = x @ p["wc"].astype(dt_c)
    dt_in = x @ p["wdt"].astype(dt_c)
    xs = jax.nn.silu(_causal_conv(xs_raw, p["conv_x"].astype(dt_c),
                                  p["conv_x_bias"].astype(dt_c)))
    bs = jax.nn.silu(_causal_conv(b_raw, p["conv_b"].astype(dt_c),
                                  p["conv_b_bias"].astype(dt_c)))
    cs = jax.nn.silu(_causal_conv(c_raw, p["conv_c"].astype(dt_c),
                                  p["conv_c_bias"].astype(dt_c)))
    xs = shard(xs.reshape(B_, S, H, P), "batch", None, "ssm_heads", None)
    bs = bs.reshape(B_, S, G, N)
    cs = cs.reshape(B_, S, G, N)
    dt = jax.nn.softplus(
        dt_in.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32)
    )                                                       # (B,S,H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))            # (H,)
    dA = dt * A                                             # (B,S,H) <= 0

    y, final_state = ssd_chunked(
        xs, bs, cs, dt, dA, chunk=min(s.chunk, S), n_heads=H,
    )
    y = y + xs * p["D"].astype(dt_c)[None, None, :, None]
    y = y.reshape(B_, S, d_in)
    y = rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = y @ p["out"].astype(dt_c)
    out = shard(out, "batch", None, "d_model")
    if return_cache:
        cw = s.d_conv - 1
        cache = {
            "conv_x": xs_raw[:, -cw:],
            "conv_b": b_raw[:, -cw:],
            "conv_c": c_raw[:, -cw:],
            "state": final_state,
        }
        return out, cache
    return out, None


def ssd_chunked(xs, bs, cs, dt, dA, *, chunk: int, n_heads: int):
    """Chunked SSD.  xs (B,S,H,P), bs/cs (B,S,G,N), dt/dA (B,S,H).

    Returns y (B,S,H,P) and final state (B,H,N,P) fp32.
    """
    B_, S, H, P = xs.shape
    G, N = bs.shape[2], bs.shape[3]
    rep = H // G
    pad = (-S) % chunk
    if pad:
        # zero-pad is exact: dA=0 -> decay exp(0)=1, x*dt=0 -> no input
        zseq = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        xs, bs, cs, dt, dA = map(zseq, (xs, bs, cs, dt, dA))
    Sp = S + pad
    nc = Sp // chunk
    Q = chunk
    dt_c = xs.dtype

    xc = xs.reshape(B_, nc, Q, H, P)
    bc = jnp.repeat(bs.reshape(B_, nc, Q, G, N), rep, axis=3)   # (B,nc,Q,H,N)
    cc = jnp.repeat(cs.reshape(B_, nc, Q, G, N), rep, axis=3)
    dtc = dt.reshape(B_, nc, Q, H)
    dAc = dA.reshape(B_, nc, Q, H)
    csum = jnp.cumsum(dAc, axis=2)                              # (B,nc,Q,H)

    xdt = (xc.astype(jnp.float32) * dtc[..., None]).astype(dt_c)
    # intra-chunk (attention form)
    cb = jnp.einsum("bcqhn,bcthn->bchqt", cc, bc,
                    preferred_element_type=jnp.float32)
    diff = csum[:, :, :, None, :] - csum[:, :, None, :, :]      # (B,nc,Q,Q,H)
    diff = jnp.moveaxis(diff, -1, 2)                            # (B,nc,H,Q,Q)
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    # mask before exp: above the diagonal diff > 0 overflows at long
    # chunks, and exp's gradient there (inf · 0) would be NaN
    decay = jnp.exp(jnp.where(mask, diff, -jnp.inf))
    y_intra = jnp.einsum(
        "bchqt,bcthp->bcqhp", (cb * decay).astype(dt_c), xdt
    )
    # chunk states
    to_end = jnp.exp(csum[:, :, -1:, :] - csum)                 # (B,nc,Q,H)
    states = jnp.einsum(
        "bcthn,bcthp->bchnp",
        (bc.astype(jnp.float32) * to_end[..., None]).astype(dt_c), xdt,
        preferred_element_type=jnp.float32,
    )                                                           # (B,nc,H,N,P)
    chunk_decay = jnp.exp(csum[:, :, -1, :])                    # (B,nc,H)

    def scan_body(h, inp):
        st, cd = inp                                            # (B,H,N,P),(B,H)
        h_next = h * cd[..., None, None] + st.astype(jnp.float32)
        return h_next, h                                        # emit h_prev

    h0 = jnp.zeros((B_, H, N, P), jnp.float32)
    final, h_prevs = jax.lax.scan(
        scan_body, h0,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
    )
    h_prev = jnp.moveaxis(h_prevs, 0, 1)                        # (B,nc,H,N,P)
    c_in = (cc.astype(jnp.float32) * jnp.exp(csum)[..., None]).astype(dt_c)
    y_inter = jnp.einsum(
        "bcqhn,bchnp->bcqhp", c_in, h_prev.astype(dt_c)
    )
    y = (y_intra + y_inter).reshape(B_, Sp, H, P)
    return (y[:, :S] if pad else y), final


def apply_mamba_decode(
    cfg: ModelConfig,
    p,
    x: jax.Array,                 # (B, d)
    cache,
):
    s, d_in, H, G, N, P = _dims(cfg)
    dt_c = cfg.cdtype
    B_ = x.shape[0]
    x = x.astype(dt_c)
    z = x @ p["wz"].astype(dt_c)
    x_raw = x @ p["wx"].astype(dt_c)
    b_raw = x @ p["wb"].astype(dt_c)
    c_raw = x @ p["wc"].astype(dt_c)
    dt_in = x @ p["wdt"].astype(dt_c)
    xs, conv_x = _conv_step(x_raw, cache["conv_x"], p["conv_x"].astype(dt_c),
                            p["conv_x_bias"].astype(dt_c))
    bs, conv_b = _conv_step(b_raw, cache["conv_b"], p["conv_b"].astype(dt_c),
                            p["conv_b_bias"].astype(dt_c))
    cs, conv_c = _conv_step(c_raw, cache["conv_c"], p["conv_c"].astype(dt_c),
                            p["conv_c_bias"].astype(dt_c))
    xs, bs, cs = jax.nn.silu(xs), jax.nn.silu(bs), jax.nn.silu(cs)
    xs = xs.reshape(B_, H, P)
    bs = jnp.repeat(bs.reshape(B_, G, N), H // G, axis=1)       # (B,H,N)
    cs = jnp.repeat(cs.reshape(B_, G, N), H // G, axis=1)
    dt = jax.nn.softplus(
        dt_in.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32)
    )                                                           # (B,H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    dA = jnp.exp(dt * A)                                        # (B,H)
    h = cache["state"]                                          # (B,H,N,P) f32
    upd = jnp.einsum("bhn,bhp->bhnp", bs.astype(jnp.float32),
                     (xs.astype(jnp.float32) * dt[..., None]))
    h = h * dA[..., None, None] + upd
    y = jnp.einsum("bhn,bhnp->bhp", cs.astype(jnp.float32), h).astype(dt_c)
    y = y + xs * p["D"].astype(dt_c)[None, :, None]
    y = y.reshape(B_, d_in)
    y = rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = y @ p["out"].astype(dt_c)
    new_cache = {
        "conv_x": conv_x, "conv_b": conv_b, "conv_c": conv_c, "state": h,
    }
    return out, new_cache
