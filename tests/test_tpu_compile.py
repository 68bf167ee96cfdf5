"""Compile rehearsals for the real chip: the main path's stencil kernels
at real widths, compiled (not interpreted) for a described TPU v5e.

Interpret mode never checks Mosaic's layout rules — aligned row slices,
lane-padded VMEM, what the compiler can broadcast — so these compiles
are the only CPU-side guard that the kernels still lower for the chip.
Nothing runs: a pass says the chip's compiler accepts the kernel, not
that its results are right (``chip_smoke.py`` checks those on the chip).

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.stencil.kernel import (
    pick_shot_tile,
    should_stream,
    wave_block_shots_pallas,
    wave_block_shots_stream_pallas,
)

K = 4          # the main path's steps per fused block


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, n_shots, nz, nx, sharding):
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    args = (sds((n_shots, nz, nx)), sds((n_shots, nz, nx)), sds((nz, nx)),
            sds((nz, nx)), sds((K,)), sds((n_shots,), jnp.int32),
            sds((n_shots,), jnp.int32))
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("n_shots,nz,nx", [
    (4, 600, 600),        # the paper's grid (Table 2)
    (16, 4096, 24),       # a pipeline-schedule boundary window at 4096²
])
def test_resident_kernel_compiles_for_v5e(one_chip, n_shots, nz, nx):
    assert not should_stream(nz, nx, K)
    tile = pick_shot_tile(n_shots, nz, nx, K)
    compiled = _compile(
        lambda p, pp, v, s, sv, z, x: wave_block_shots_pallas(
            p, pp, v, s, sv, z, x, receiver_row=2, interpret=False),
        tile, nz, nx, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_shots,nz,nx,in_grid,tiles", [
    # the production grid (DESIGN.md §15): one shot tile alone
    pytest.param(16, 4096, 4096, False, 1, id="16-4096-4096"),
    # one of its four stripes
    pytest.param(16, 4096, 1024, False, 1, id="16-4096-1024"),
    # the production grid's whole batch, its tiles walked in the grid
    pytest.param(16, 4096, 4096, True, 4, id="16-4096-4096-tiles-in-grid"),
    # the 13601 x 2801 survey grid as the program pads it
    # (``stripe_geometry``): one 13,696-lane stripe, shot tiles of 1 ...
    pytest.param(12, 2808, 13696, True, 12, id="12-2808-13696-tiles-in-grid"),
    # ... and one of its four 3,456-lane stripes, shot tiles of 6
    pytest.param(12, 2808, 3456, True, 2, id="12-2808-3456-tiles-in-grid"),
])
def test_streamed_kernel_compiles_for_v5e(one_chip, n_shots, nz, nx,
                                          in_grid, tiles):
    assert should_stream(nz, nx, K)
    tile = pick_shot_tile(n_shots, nz, nx, K, stream=True)
    batch = n_shots if in_grid else tile
    assert batch // tile == tiles
    compiled = _compile(
        lambda p, pp, v, s, sv, z, x: wave_block_shots_stream_pallas(
            p, pp, v, s, sv, z, x, receiver_row=2, interpret=False,
            shot_tile=tile),
        batch, nz, nx, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
