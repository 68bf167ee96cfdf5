"""Kernel micro-benchmarks.

Interpret-mode vs compiled semantics: off-TPU, the Pallas kernels run in
INTERPRET mode (auto-selected by ``stencil.kernel.default_interpret``) —
the kernel body executes with real Pallas semantics (BlockSpec tiling,
halo views, @pl.when predication are all exercised), but each grid step
is a Python-driven emulation, so absolute ``*_pallas_*`` times here are
one to two orders of magnitude above both the compiled-TPU times and the
XLA-fused ``*_ref_*`` rows.  They are regression trackers for the
kernels' *structure* (a tiling bug usually shows up as a blow-up), NOT
TPU projections — those come from the roofline analysis.  On a TPU
backend the same rows time the compiled kernels and are directly
comparable.  Each row also emits the kernel's arithmetic-intensity
estimate (flops/byte) used to pick block shapes.  The stencil kernels
are measured on the chip by ``bench/`` (DESIGN.md §15)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.ops import attention
from repro.kernels.rmsnorm.ops import rmsnorm_residual
from repro.kernels.ssd.ops import ssd_chunk


def _time(fn, *args, n=3, **kw):
    fn(*args, **kw)  # compile
    jax.block_until_ready(fn(*args, **kw))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


def run() -> list[str]:
    rows = []
    # flash attention 1x4x512x64
    q = jnp.ones((1, 4, 512, 64), jnp.float32)
    k = jnp.ones((1, 2, 512, 64), jnp.float32)
    us_ref = _time(attention, q, k, k, causal=True)
    us_pal = _time(attention, q, k, k, causal=True, use_pallas=True,
                   bq=128, bk=128)
    ai = (2 * 512 * 64) / (3 * 64 * 4)  # per-row flops/bytes order
    rows += [
        f"kernels.flash_ref_512,{us_ref:.0f},{ai:.1f}",
        f"kernels.flash_pallas_512,{us_pal:.0f},{ai:.1f}",
    ]
    # ssd chunk (8,4,128,64,64)
    xdt = jnp.ones((8, 4, 128, 64), jnp.float32)
    bm = jnp.ones((8, 4, 128, 64), jnp.float32)
    cs = -jnp.cumsum(jnp.full((8, 4, 128), 0.01), -1)
    us_ref = _time(ssd_chunk, xdt, bm, bm, cs)
    us_pal = _time(ssd_chunk, xdt, bm, bm, cs, use_pallas=True)
    rows += [
        f"kernels.ssd_ref_128,{us_ref:.0f},64",
        f"kernels.ssd_pallas_128,{us_pal:.0f},64",
    ]
    # rmsnorm 4096x1024
    x = jnp.ones((4096, 1024), jnp.float32)
    sc = jnp.ones((1024,), jnp.float32)
    us_ref = _time(rmsnorm_residual, x, x, sc)
    us_pal = _time(rmsnorm_residual, x, x, sc, use_pallas=True, bn=256)
    rows += [
        f"kernels.rmsnorm_ref_4kx1k,{us_ref:.0f},0.6",
        f"kernels.rmsnorm_pallas_4kx1k,{us_pal:.0f},0.6",
    ]
    return rows
