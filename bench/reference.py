"""Plain reference of the FWI forward propagation, written from the
published description and sharing no code with the program.

The 2-D acoustic wave equation, second order in time and fourth order
in space, over a layered velocity model with a salt dome, a Cerjan
sponge on all four edges and zero values beyond them, and one Ricker
point source per shot:

    p_next = (2·p - p_prev + (v·dt/dx)²·∇²p) · sponge,   then + source
    p_prev' = p · sponge

``∇²`` is the central difference [-1/12, 4/3, -5/2, 4/3, -1/12] along
each axis.  ``p_prev`` is the sponge-damped previous field, as the
state of a restart holds it.  The source adds ``w[t]·dt²`` at the
shot's position after the sponge, where ``w`` is a 12 Hz Ricker wavelet
sampled at ``t·dt`` for ``t`` below the configuration's ``timesteps``
and held at its last sample after.

Every step is plain ``jax.numpy`` on whole arrays, one timestep at a
time, in the dtype asked for: float32 is the reference, and bfloat16 the
control that a comparison must fail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

C0, C1, C2 = -5.0 / 2.0, 4.0 / 3.0, -1.0 / 12.0


def velocity(fwi: dict) -> np.ndarray:
    """Layered model with a salt dome, in m/s: 1500 + 2.2 m/s per row,
    +400 below a third and +500 below half of the depth, and a 4500 m/s
    ellipse centred at 62% of the depth and half the width, with
    semi-axes of 18% of the depth and 25% of the width."""
    nz, nx = fwi["nz"], fwi["nx"]
    z = np.arange(nz)[:, None]
    x = np.arange(nx)[None, :]
    v = 1500.0 + 2.2 * z + 400.0 * (z > nz // 3) + 500.0 * (z > nz // 2)
    cz, cx = int(nz * 0.62), int(nx * 0.5)
    dome = ((z - cz) / (0.18 * nz)) ** 2 + ((x - cx) / (0.25 * nx)) ** 2
    return np.where(dome < 1.0, 4500.0, v + 0.0 * x).astype(np.float32)


def sponge(fwi: dict) -> np.ndarray:
    """exp(-(a·(w - d))²) within ``w`` cells of an edge, 1 elsewhere,
    where ``d`` is the distance in cells to the nearest edge."""
    nz, nx, w = fwi["nz"], fwi["nx"], fwi["sponge_width"]
    dz = np.minimum(np.arange(nz), nz - 1 - np.arange(nz))[:, None]
    dx = np.minimum(np.arange(nx), nx - 1 - np.arange(nx))[None, :]
    d = np.minimum(np.minimum(dz, dx), w).astype(np.float64)
    taper = np.exp(-(fwi["sponge_strength"] * (w - d)) ** 2)
    return np.where(d >= w, 1.0, taper).astype(np.float32)


def wavelet(fwi: dict) -> np.ndarray:
    """Ricker wavelet of ``source_freq`` Hz delayed by 1.2 periods,
    scaled by 1e3, one sample per timestep."""
    t = np.arange(fwi["timesteps"]) * fwi["dt"]
    a = (np.pi * fwi["source_freq"] * (t - 1.2 / fwi["source_freq"])) ** 2
    return ((1.0 - 2.0 * a) * np.exp(-a) * 1e3).astype(np.float32)


def sources(fwi: dict) -> tuple[np.ndarray, np.ndarray]:
    """Per-shot source row and column: row 4, columns evenly spread
    from 20% to 80% of the width, truncated to whole cells."""
    xs = np.linspace(fwi["nx"] * 0.2, fwi["nx"] * 0.8, fwi["n_shots"])
    return np.full(fwi["n_shots"], 4, np.int32), xs.astype(np.int32)


def laplacian(p: jnp.ndarray) -> jnp.ndarray:
    """Unscaled 4th-order Laplacian of (..., nz, nx), zero outside."""
    nz, nx = p.shape[-2:]
    q = jnp.pad(p, [(0, 0)] * (p.ndim - 2) + [(2, 2), (2, 2)])

    def at(dz, dx):
        return q[..., 2 + dz: 2 + dz + nz, 2 + dx: 2 + dx + nx]

    out = (2.0 * C0) * p
    for d, c in ((1, C1), (2, C2)):
        out = out + c * (at(-d, 0) + at(d, 0) + at(0, -d) + at(0, d))
    return out


def _propagate(p, p_prev, v2dt2, taper, amp, t0, steps, src_z, src_x):
    """``steps`` timesteps of every shot in (S, nz, nx) from time
    ``t0``; the step count is traced, so one program serves any."""
    shot = jnp.arange(p.shape[0])
    last = amp.shape[0] - 1

    def step(i, carry):
        p, p_prev = carry
        nxt = (2.0 * p - p_prev + v2dt2 * laplacian(p)) * taper
        nxt = nxt.at[shot, src_z, src_x].add(amp[jnp.minimum(t0 + i, last)])
        return nxt, p * taper

    return jax.lax.fori_loop(0, steps, step, (p, p_prev))


@functools.lru_cache(maxsize=8)
def _runner(devices: tuple):
    """The jitted propagation, with the shot axis split over
    ``devices`` (each device steps its own shots, nothing crosses)."""
    if len(devices) == 1:
        return jax.jit(_propagate)
    mesh = Mesh(np.asarray(devices), ("shot",))
    sh = P("shot")
    return jax.jit(jax.shard_map(
        _propagate, mesh=mesh,
        in_specs=(sh, sh, P(), P(), P(), P(), P(), sh, sh),
        out_specs=(sh, sh), check_vma=False,
    ))


def propagate(fwi: dict, p, p_prev, shots, t0: int, steps: int, *,
              dtype=jnp.float32, devices=None):
    """Advance shots ``shots`` (indices into the configuration's shot
    list) of the fields ``p``, ``p_prev`` (one row per listed shot) by
    ``steps`` timesteps from time ``t0``, in ``dtype``.  With several
    ``devices`` the shots are spread over them; their count must divide
    the number of shots.  Returns float32 (p, p_prev)."""
    devices = tuple(devices or jax.devices()[:1])
    shots = np.asarray(shots)
    if len(shots) % len(devices):
        raise ValueError(f"{len(shots)} shots do not split over "
                         f"{len(devices)} devices")
    v = jnp.asarray(velocity(fwi))
    v2dt2 = ((v * fwi["dt"] / fwi["dx"]) ** 2).astype(dtype)
    taper = jnp.asarray(sponge(fwi), dtype)
    amp = (jnp.asarray(wavelet(fwi)) * (fwi["dt"] ** 2)).astype(dtype)
    src_z, src_x = (jnp.asarray(a[shots]) for a in sources(fwi))
    if len(devices) > 1:
        put = NamedSharding(Mesh(np.asarray(devices), ("shot",)), P("shot"))
        p, p_prev, src_z, src_x = jax.device_put(
            (p, p_prev, src_z, src_x), put)
    run = _runner(devices)
    out = run(jnp.asarray(p, dtype), jnp.asarray(p_prev, dtype), v2dt2,
              taper, amp, jnp.int32(t0), jnp.int32(steps), src_z, src_x)
    return tuple(a.astype(jnp.float32) for a in out)
