"""Shot-batched stencil engine (DESIGN.md §17): parity, VMEM
accounting, tiling, dispatch, and the uneven shot split."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.stencil.kernel import (
    DEFAULT_VMEM_BUDGET,
    HALO,
    pick_bz_stream,
    pick_shot_tile,
    resident_vmem_bytes,
    should_stream,
    stream_vmem_bytes,
    wave_block_shots_pallas,
    wave_block_shots_stream_pallas,
)
from repro.kernels.stencil.ops import wave_block
from repro.kernels.stencil.ref import (
    wave_block_ref,
    wave_block_shots_ref,
    wave_block_shots_strips_ref,
    wave_block_strips_ref,
)

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _case(S, nz, nx, k, *, per_shot_src=False, seed=0):
    ks = jax.random.split(jax.random.key(seed + 7 * S + nz + nx), 7)
    p = jax.random.normal(ks[0], (S, nz, nx), jnp.float32)
    pp = jax.random.normal(ks[1], (S, nz, nx), jnp.float32)
    v = jax.random.uniform(ks[2], (nz, nx), jnp.float32, 0.05, 0.2)
    sp = jnp.clip(jax.random.uniform(ks[3], (nz, nx)), 0.9, 1.0)
    if per_shot_src:
        srcv = jax.random.normal(ks[4], (S, k), jnp.float32)
    else:
        srcv = jnp.linspace(0.5, 1.0, k, dtype=jnp.float32)
    sz = jax.random.randint(ks[5], (S,), HALO, nz - HALO)
    sx = jax.random.randint(ks[6], (S,), 0, nx)
    return p, pp, v, sp, srcv, sz, sx


def _vmap_ref(p, pp, v, sp, srcv, sz, sx, rrow):
    """The pre-batching semantics: one ``wave_block_ref`` per shot."""
    svb = srcv if srcv.ndim == 2 else \
        jnp.broadcast_to(srcv, (p.shape[0],) + srcv.shape)

    def one(a, b, sv, zi, xi):
        return wave_block_ref(a, b, v, sp, sv, zi, xi, receiver_row=rrow)

    return jax.vmap(one, (0, 0, 0, 0, 0))(p, pp, svb, sz, sx)


# ------------------------------------------------- XLA mirrors: bitwise


@pytest.mark.parametrize("S", [1, 2, 3, 4])
@pytest.mark.parametrize("per_shot_src", [False, True])
def test_shots_ref_bitwise_vs_vmap(S, per_shot_src):
    p, pp, v, sp, srcv, sz, sx = _case(S, 48, 64, 4,
                                       per_shot_src=per_shot_src)
    ref = _vmap_ref(p, pp, v, sp, srcv, sz, sx, 3)
    out = wave_block_shots_ref(p, pp, v, sp, srcv, sz, sx,
                               receiver_row=3)
    for a, b in zip(ref, out):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("S,bz", [(1, 8), (3, 16), (4, 8)])
def test_shots_strips_ref_bitwise(S, bz):
    p, pp, v, sp, srcv, sz, sx = _case(S, 48, 64, 2)
    whole = wave_block_shots_ref(p, pp, v, sp, srcv, sz, sx,
                                 receiver_row=5)
    strips = wave_block_shots_strips_ref(p, pp, v, sp, srcv, sz, sx,
                                         receiver_row=5, bz=bz)

    def one(a, b, zi, xi):
        return wave_block_strips_ref(a, b, v, sp, srcv, zi, xi,
                                     receiver_row=5, bz=bz)

    vm = jax.vmap(one, (0, 0, 0, 0))(p, pp, sz, sx)
    for a, b, c in zip(whole, strips, vm):
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert np.array_equal(np.asarray(b), np.asarray(c))


# --------------------------------------------- Pallas (interpret): 1e-5


@pytest.mark.parametrize("S", [1, 2, 3, 4])
def test_shots_pallas_matches_ref(S):
    p, pp, v, sp, srcv, sz, sx = _case(S, 64, 128, 4)
    ref = _vmap_ref(p, pp, v, sp, srcv, sz, sx, 7)
    out = wave_block_shots_pallas(p, pp, v, sp, srcv, sz, sx,
                                  receiver_row=7, bz=16)
    for a, b in zip(ref, out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5)


def test_shots_stream_bitwise_vs_resident():
    S = 3
    p, pp, v, sp, srcv, sz, sx = _case(S, 64, 128, 4)
    res = wave_block_shots_pallas(p, pp, v, sp, srcv, sz, sx,
                                  receiver_row=7, bz=16)
    stm = wave_block_shots_stream_pallas(p, pp, v, sp, srcv, sz, sx,
                                         receiver_row=7, bz=16)
    for a, b in zip(res, stm):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("S,tile", [(4, 1), (4, 2), (6, 3)])
@pytest.mark.parametrize("per_shot_src", [False, True])
def test_shots_stream_walks_tiles_in_grid_bitwise(S, tile, per_shot_src):
    """The streamed kernel walking S / tile shot tiles in its grid
    equals the whole batch in one tile, and each tile run alone and
    concatenated, bitwise: every shot's source sits in its own tile."""
    p, pp, v, sp, srcv, sz, sx = _case(S, 64, 128, 4,
                                       per_shot_src=per_shot_src)
    kw = dict(receiver_row=7, bz=16)
    tiled = wave_block_shots_stream_pallas(p, pp, v, sp, srcv, sz, sx,
                                           shot_tile=tile, **kw)
    whole = wave_block_shots_stream_pallas(p, pp, v, sp, srcv, sz, sx,
                                           shot_tile=S, **kw)
    parts = [
        wave_block_shots_stream_pallas(
            p[lo:lo + tile], pp[lo:lo + tile], v, sp,
            srcv[lo:lo + tile] if per_shot_src else srcv,
            sz[lo:lo + tile], sx[lo:lo + tile], **kw)
        for lo in range(0, S, tile)
    ]
    joined = [np.concatenate([np.asarray(o[i]) for o in parts])
              for i in range(3)]
    for a, b, c in zip(tiled, whole, joined):
        assert a.shape == b.shape == c.shape
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert np.array_equal(np.asarray(a), c)


def test_streamed_dispatch_neither_splits_nor_joins_the_batch():
    """A dividing tile on the streamed Pallas path is one call on the
    whole batch: no tile-split or tile-concat scope in the lowered
    program.  A ragged tile still slices and joins, which shows the
    scopes would be seen."""
    s, nz, nx, k = 4, 64, 128, 4
    f = jnp.zeros((s, nz, nx), jnp.float32)
    m = jnp.ones((nz, nx), jnp.float32)
    at = jnp.zeros((s,), jnp.int32)

    def lowered(tile):
        return jax.jit(lambda p, pp: wave_block(
            p, pp, 0.1 * m, m, jnp.ones((k,)), at + 8, at + 16,
            use_pallas=True, stream=True, bz=16, shot_tile=tile,
        )).lower(f, f).as_text(debug_info=True)

    walked, ragged = lowered(2), lowered(3)
    assert "stencil.tile_split" not in walked
    assert "stencil.tile_concat" not in walked
    assert "stencil.tile_split" in ragged
    assert "stencil.tile_concat" in ragged


# ------------------------------------------- dispatch + unaligned tiles


@pytest.mark.parametrize("tile", [1, 2, 3, 4])
def test_dispatch_xla_shot_tile_bitwise(tile):
    """Any tile — divisor or ragged — is value-preserving on XLA."""
    p, pp, v, sp, srcv, sz, sx = _case(4, 48, 64, 2)
    full = wave_block_shots_ref(p, pp, v, sp, srcv, sz, sx,
                                receiver_row=3)
    tiled = wave_block(p, pp, v, sp, srcv, sz, sx, receiver_row=3,
                       shot_tile=tile)
    for a, b in zip(full, tiled):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("use_pallas", [False, True])
def test_wave_block_refuses_2d_fields(use_pallas):
    """One shot is the batch S=1: a bare (NZ, NX) field is refused, not
    run through a kernel of its own."""
    p, pp, v, sp, srcv, sz, sx = _case(1, 48, 64, 2)
    with pytest.raises(ValueError, match="S, NZ, NX"):
        wave_block(p[0], pp[0], v, sp, srcv, sz[0], sx[0],
                   use_pallas=use_pallas)


@pytest.mark.parametrize("tile", [3, 4])
def test_dispatch_pallas_shot_tile(tile):
    """Unaligned Pallas tiles run a remainder tile; per-shot math is
    identical at any batch size, so tilings agree bitwise with each
    other and to 1e-5 with the XLA reference."""
    p, pp, v, sp, srcv, sz, sx = _case(4, 64, 128, 4)
    ref = _vmap_ref(p, pp, v, sp, srcv, sz, sx, 7)
    out = wave_block(p, pp, v, sp, srcv, sz, sx, receiver_row=7,
                     use_pallas=True, bz=16, stream=False,
                     shot_tile=tile)
    whole = wave_block(p, pp, v, sp, srcv, sz, sx, receiver_row=7,
                       use_pallas=True, bz=16, stream=False, shot_tile=4)
    for a, b, c in zip(ref, out, whole):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5)
        assert np.array_equal(np.asarray(b), np.asarray(c))


# --------------------------------------------------- s-aware VMEM model


def test_vmem_formulas_reduce_at_s1():
    nz, nx, bz, k = 600, 600, 120, 8
    lanes = 640                  # VMEM pads each 600-float row to 5×128
    # the pre-§17 single-shot accounting, written out long-hand
    assert resident_vmem_bytes(nz, nx, k, bz=bz) == \
        4 * (4 * nz * lanes + 4 * bz * lanes + k * lanes)
    # streamed: two slots of four windows, double-buffered output
    # strips and a double-buffered trace block
    win = min(bz + 2 * k * HALO, nz)
    assert stream_vmem_bytes(nz, nx, bz, k) == \
        4 * (2 * 4 * win * lanes + 4 * bz * lanes + 2 * k * lanes)


def test_vmem_monotone_in_s():
    nz, nx, bz, k = 256, 256, 32, 4
    res = [resident_vmem_bytes(nz, nx, k, bz=bz, s=s) for s in (1, 2, 4)]
    stm = [stream_vmem_bytes(nz, nx, bz, k, s=s) for s in (1, 2, 4)]
    assert res == sorted(res) and len(set(res)) == 3
    assert stm == sorted(stm) and len(set(stm)) == 3
    # the model fields are charged ONCE per batch: doubling s less than
    # doubles the bytes (the whole point of the shared slot)
    assert res[1] < 2 * res[0] and stm[1] < 2 * stm[0]


def test_pick_bz_stream_s_aware():
    bz1 = pick_bz_stream(1536, 1536, 4)
    bz2 = pick_bz_stream(1536, 1536, 4, s=2)
    assert bz2 <= bz1
    assert stream_vmem_bytes(1536, 1536, bz2, 4, s=2) \
        <= DEFAULT_VMEM_BUDGET
    with pytest.raises(ValueError):
        pick_bz_stream(1536, 1536, 4, vmem_budget=64 * 1024, s=2)


def test_should_stream_s_aware():
    assert not should_stream(600, 600, 8)
    assert should_stream(600, 600, 8, s=4)
    assert should_stream(2048, 2048, 4)


def test_pick_shot_tile():
    # 600² k=8: s=4 blows the 16 MiB resident budget, s=2 fits
    t = pick_shot_tile(4, 600, 600, 8, bz=120)
    assert t == 2
    assert resident_vmem_bytes(600, 600, 8, bz=120, s=t) \
        <= DEFAULT_VMEM_BUDGET
    assert resident_vmem_bytes(600, 600, 8, bz=120, s=4) \
        > DEFAULT_VMEM_BUDGET
    # a small grid takes the whole batch; a starved budget degrades to 1
    assert pick_shot_tile(4, 64, 64, 4, bz=16) == 4
    assert pick_shot_tile(4, 600, 600, 8, bz=120,
                          vmem_budget=1024) == 1
    # only divisors are picked by default (no ragged tiles)
    assert 6 % pick_shot_tile(6, 600, 600, 8, bz=120) == 0


def test_shot_parallel_runner_single_device():
    """n_devices=1 runs in-process (no forced device count), pinning
    the sharded runner against the plain block runner."""
    from repro.fwi.solver import (
        FWIConfig, ShotState, make_block_runner,
        make_shot_parallel_runner,
    )

    cfg = FWIConfig(nz=48, nx=64, timesteps=8, n_shots=3,
                    sponge_width=4)
    st = ShotState.init(cfg)
    run_sp, place = make_shot_parallel_runner(cfg, 1, k=4)
    ref_run = make_block_runner(cfg, k=4)
    a = run_sp(*place((st.p, st.p_prev)), 0, 8)
    b = ref_run(st.p, st.p_prev, 0, 8)
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-6, atol=1e-6)


# ------------------------------------- uneven shot split across devices

_UNEVEN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp, numpy as np
from repro.core import Resources, PodSpec
from repro.fwi.driver import elastic_stripes_for
from repro.fwi.solver import FWIConfig, ShotState, make_shot_parallel_runner

assert jax.device_count() >= 4
cfg = FWIConfig(nz=48, nx=64, timesteps=16, n_shots=4, sponge_width=4)
st = ShotState.init(cfg)

# the elastic GROW decides the device count: a burst pod re-splits the
# shot axis to 3 devices, a non-divisor of the 4-shot batch
grown = elastic_stripes_for(1, 3)(
    Resources(pods=[PodSpec(chips=1, name="cluster"),
                    PodSpec(chips=1, name="burst")],
              shares=[0.5, 0.5]))
assert grown == 3

run1, place1 = make_shot_parallel_runner(cfg, 1, k=4)
run3, place3 = make_shot_parallel_runner(cfg, grown, k=4)
o1 = run1(*place1((st.p, st.p_prev)), 0, 16)
o3 = run3(*place3((st.p, st.p_prev)), 0, 16)
for a, b in zip(o1, o3):
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape and a.shape[0] == cfg.n_shots, (a.shape,
                                                              b.shape)
    # documented contract: f32-ULP equal (1e-6 relative), not bitwise
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
print("uneven-split OK")
"""


def test_uneven_shot_split_matches_single_device():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _UNEVEN, SRC],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, (out.stdout[-1000:], out.stderr[-3000:])
    assert "uneven-split OK" in out.stdout
