"""Seeded initial wavefields: the state of a survey in mid-propagation.

Each shot's ``p`` is a sum of ``modes`` plane waves with wavelengths
drawn uniformly from the mix's band, directions and phases uniform, and
an RMS amplitude of ``amplitude``; its ``p_prev`` is the same waves one
timestep earlier at a nominal speed.  The energy covers the whole grid,
so every strip seam of the kernel and every stripe seam of the striped
domain carries wave traffic from the first step.

A plane wave is separable, sin(kz·z + kx·x + φ) = sin(kz·z + φ)·cos(kx·x)
+ cos(kz·z + φ)·sin(kx·x), so a shot's field is one (nz, 2M) × (2M, nx)
product: one jitted call on the device makes every shot, from the seed
alone, in float32.  Shot ``s`` depends only on the seed and ``s``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=(
    "shots", "nz", "nx", "modes", "dx", "dt", "band_m", "speed_m_s",
    "amplitude"))
def _fields(seed_hi, seed_lo, *, shots: int, nz: int, nx: int,
            modes: int, dx: float, dt: float, band_m: tuple,
            speed_m_s: float, amplitude: float):
    key = jax.random.fold_in(jax.random.key(seed_hi), seed_lo)
    z = jnp.arange(nz, dtype=jnp.float32)[:, None]
    x = jnp.arange(nx, dtype=jnp.float32)[None, :]

    def one(s):
        kl, kd, kp = jax.random.split(
            jax.random.fold_in(key, s), 3)
        lam = jax.random.uniform(kl, (modes,), minval=band_m[0],
                                 maxval=band_m[1])
        ang = jax.random.uniform(kd, (modes,), maxval=2 * jnp.pi)
        phi = jax.random.uniform(kp, (modes,), maxval=2 * jnp.pi)
        k = 2 * jnp.pi * dx / lam                  # radians per cell
        kz, kx = k * jnp.cos(ang), k * jnp.sin(ang)
        amp = amplitude * jnp.sqrt(2.0 / modes)

        def field(ph):
            a = jnp.concatenate([jnp.sin(kz * z + ph),
                                 jnp.cos(kz * z + ph)], axis=1) * amp
            # a: (nz, 2M)
            b = jnp.concatenate([jnp.cos(kx[:, None] * x),
                                 jnp.sin(kx[:, None] * x)])  # (2M, nx)
            return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)

        # one step earlier the wave stood ω·dt further back in phase
        omega_dt = 2 * jnp.pi * speed_m_s * dt / lam
        return field(phi), field(phi + omega_dt)

    return jax.vmap(one)(jnp.arange(shots))


def initial_fields(seed: int, *, shots: int, nz: int, nx: int,
                   init: dict, dx: float, dt: float):
    """(p, p_prev), each (shots, nz, nx) float32, from a non-negative
    ``seed`` of up to 64 bits and the mix's ``init`` parameters."""
    hi, lo = divmod(int(seed), 2 ** 32)
    if not 0 <= hi < 2 ** 32:
        raise ValueError(f"seed {seed} is not a 64-bit whole number")
    return _fields(
        np.uint32(hi), np.uint32(lo), shots=shots, nz=nz, nx=nx,
        modes=int(init["modes"]), dx=float(dx), dt=float(dt),
        band_m=tuple(float(b) for b in init["wavelength_m"]),
        speed_m_s=float(init["speed_m_s"]),
        amplitude=float(init["amplitude"]))
