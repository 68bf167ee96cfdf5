"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import attention
from repro.kernels.rmsnorm.ops import rmsnorm_residual
from repro.kernels.ssd.ops import ssd_chunk
from repro.kernels.stencil.ops import wave_block
from repro.kernels.stencil.ref import wave_step_ref

# --------------------------------------------------------------- stencil


def _one_step(p, pp, v, sponge, **kw):
    """One step of one shot through ``wave_block`` (the batch S=1, k=1,
    no source): its (p_next, p_damped)."""
    z = jnp.zeros((1,), jnp.int32)
    pn, pd, _ = wave_block(p[None], pp[None], v, sponge,
                           jnp.zeros((1,)), z, z, **kw)
    return pn[0], pd[0]


_SHAPES = [(256, 256, 128), (128, 384, 32), (512, 128, 64), (64, 640, 8)]


@pytest.mark.parametrize("nz,nx,bz,stream", [
    pytest.param(*shape, False, id="-".join(map(str, shape)))
    for shape in _SHAPES
] + [
    pytest.param(*shape, True, id="stream-" + "-".join(map(str, shape)))
    for shape in _SHAPES
])
def test_stencil_matches_ref(nz, nx, bz, stream):
    """The resident and the streamed kernel, one shot and one step, vs
    the reference step."""
    ks = jax.random.split(jax.random.key(nz + nx), 4)
    p = jax.random.normal(ks[0], (nz, nx), jnp.float32)
    pp = jax.random.normal(ks[1], (nz, nx), jnp.float32)
    v = jax.random.uniform(ks[2], (nz, nx), jnp.float32, 0.05, 0.2)
    sponge = jnp.clip(jax.random.uniform(ks[3], (nz, nx)), 0.9, 1.0)
    a1, a2 = wave_step_ref(p, pp, v, sponge)
    b1, b2 = _one_step(p, pp, v, sponge, use_pallas=True, bz=bz,
                       stream=stream)
    np.testing.assert_allclose(a1, b1, atol=3e-6)
    np.testing.assert_allclose(a2, b2, atol=3e-6)


def test_stencil_boundary_rows_match_ref():
    """First/last strips must use zero halo exactly like the ref."""
    nz, nx = 64, 128
    p = jnp.ones((nz, nx), jnp.float32)
    pp = jnp.zeros_like(p)
    v = jnp.full_like(p, 0.1)
    sponge = jnp.ones_like(p)
    a, _ = wave_step_ref(p, pp, v, sponge)
    b, _ = _one_step(p, pp, v, sponge, use_pallas=True, bz=8)
    np.testing.assert_allclose(a[:4], b[:4], atol=1e-6)
    np.testing.assert_allclose(a[-4:], b[-4:], atol=1e-6)


# --------------------------------------------------------- flash attention


@pytest.mark.parametrize("B,H,KH,S,D,bq,bk", [
    (2, 4, 2, 256, 64, 128, 128),
    (1, 8, 8, 128, 128, 64, 64),
    (2, 4, 1, 64, 32, 32, 32),
    (1, 2, 2, 512, 64, 128, 256),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(B, H, KH, S, D, bq, bk, dtype):
    ks = jax.random.split(jax.random.key(S + H), 3)
    q = jax.random.normal(ks[0], (B, H, S, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, KH, S, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, KH, S, D)).astype(dtype)
    a = attention(q, k, v, causal=True)
    b = attention(q, k, v, causal=True, use_pallas=True, bq=bq, bk=bk)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32), atol=tol
    )


def test_flash_attention_non_causal():
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 128, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 128, 64), jnp.float32)
    a = attention(q, k, v, causal=False)
    b = attention(q, k, v, causal=False, use_pallas=True, bq=64, bk=64)
    np.testing.assert_allclose(a, b, atol=2e-5)


# --------------------------------------------------------------------- ssd


@pytest.mark.parametrize("BC,H,Q,N,P", [
    (4, 2, 64, 32, 64), (2, 4, 128, 128, 64), (3, 1, 32, 16, 16),
])
def test_ssd_chunk_matches_ref(BC, H, Q, N, P):
    ks = jax.random.split(jax.random.key(Q + N), 4)
    xdt = jax.random.normal(ks[0], (BC, H, Q, P), jnp.float32)
    b = jax.random.normal(ks[1], (BC, H, Q, N), jnp.float32)
    c = jax.random.normal(ks[2], (BC, H, Q, N), jnp.float32)
    csum = -jnp.cumsum(jax.random.uniform(ks[3], (BC, H, Q)), axis=-1)
    y1, s1 = ssd_chunk(xdt, b, c, csum)
    y2, s2 = ssd_chunk(xdt, b, c, csum, use_pallas=True)
    np.testing.assert_allclose(y1, y2, atol=1e-5)
    np.testing.assert_allclose(s1, s2, atol=1e-5)


def test_ssd_chunked_equals_naive_recurrence():
    """The full chunked SSD algorithm (models/mamba2.ssd_chunked) against
    a literal sequential state-space recurrence."""
    from repro.models.mamba2 import ssd_chunked

    B_, S, H, P, N, chunk = 2, 48, 2, 16, 8, 16
    ks = jax.random.split(jax.random.key(0), 4)
    xs = jax.random.normal(ks[0], (B_, S, H, P), jnp.float32) * 0.5
    bs = jax.random.normal(ks[1], (B_, S, 1, N), jnp.float32) * 0.5
    cs = jax.random.normal(ks[2], (B_, S, 1, N), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B_, S, H)))
    A = -jnp.exp(jax.random.normal(jax.random.key(9), (H,)) * 0.3)
    y_fast, state_fast = ssd_chunked(
        xs, bs, cs, dt, dt * A, chunk=chunk, n_heads=H
    )
    h = np.zeros((B_, H, N, P))
    ys = np.zeros((B_, S, H, P))
    xsn, bsn, csn, dtn, dAn = map(
        np.asarray, (xs, bs, cs, dt, dt * A)
    )
    for t in range(S):
        for b_ in range(B_):
            for hh in range(H):
                h[b_, hh] = np.exp(dAn[b_, t, hh]) * h[b_, hh] + np.outer(
                    bsn[b_, t, 0], dtn[b_, t, hh] * xsn[b_, t, hh]
                )
                ys[b_, t, hh] = csn[b_, t, 0] @ h[b_, hh]
    np.testing.assert_allclose(y_fast, ys, atol=5e-5)
    np.testing.assert_allclose(state_fast, h, atol=5e-5)


def test_ssd_chunked_grads_finite_at_long_chunks():
    """At mamba2-370m's chunk (256) with its fastest-decaying head, the
    cumulative decay spans hundreds of e-folds: exp() above the causal
    diagonal overflows, and its gradient must not turn inf·0 into NaN."""
    from repro.models.mamba2 import ssd_chunked

    B_, S, H, P, N, chunk = 1, 256, 2, 8, 8, 256
    ks = jax.random.split(jax.random.key(1), 4)
    xs = jax.random.normal(ks[0], (B_, S, H, P), jnp.float32)
    bs = jax.random.normal(ks[1], (B_, S, 1, N), jnp.float32)
    cs = jax.random.normal(ks[2], (B_, S, 1, N), jnp.float32)
    dt = jnp.full((B_, S, H), 0.1, jnp.float32)
    A = -jnp.array([1.0, 32.0])          # A_log init spans log(1..H)

    def loss(xs, dt):
        y, st = ssd_chunked(xs, bs, cs, dt, dt * A, chunk=chunk, n_heads=H)
        return jnp.sum(y) + jnp.sum(st)

    gx, gdt = jax.grad(loss, argnums=(0, 1))(xs, dt)
    assert np.isfinite(np.asarray(gx)).all()
    assert np.isfinite(np.asarray(gdt)).all()


# ----------------------------------------------------------------- rmsnorm


@pytest.mark.parametrize("N,d,bn", [(512, 256, 128), (64, 640, 8),
                                    (256, 1024, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_ref(N, d, bn, dtype):
    x = jax.random.normal(jax.random.key(1), (N, d)).astype(dtype)
    r = jax.random.normal(jax.random.key(2), (N, d)).astype(dtype)
    sc = jax.random.normal(jax.random.key(3), (d,), jnp.float32)
    a1, a2 = rmsnorm_residual(x, r, sc)
    b1, b2 = rmsnorm_residual(x, r, sc, use_pallas=True, bn=bn)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(a1, np.float32), np.asarray(b1, np.float32), atol=tol
    )
    np.testing.assert_allclose(
        np.asarray(a2, np.float32), np.asarray(b2, np.float32), atol=tol
    )
