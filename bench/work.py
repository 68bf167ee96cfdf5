"""Work done by the FWI stencil, counted from shapes, and the chip peaks
it is measured against.

One k-step block of the shot-batched 4th-order acoustic stencil has to
read, per shot, the wavefield ``p`` and the damped previous field
``p_prev`` and write both back, and read the two model fields
(``v2dt2`` and the sponge taper) once for the whole batch:
``(2S + 2)`` field reads and ``2S`` field writes of ``nz·nx·4`` bytes,
``(4S + 2)`` fields in all, whatever implements it.  That is the least
HBM traffic of a block: a kernel that reads more (model fields per shot
tile, halo rows, concatenated tiles) moves more than this count.

Operations per grid-point update, as the reference computes them: the
Laplacian's 9 slices (3 multiplies by its coefficients and 8 adds), the
leapfrog ``(2p - p_prev + v2dt2·lap)·sponge`` (2 multiplies, 2 adds and
the sponge multiply) and the damped copy ``p·sponge`` (1 multiply):
17 float32 operations.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"

#: float32 operations per grid-point update (see the module docstring)
OPS_PER_POINT = 17
#: bytes per float32 field element
F32 = 4


def block_fields(shots: int) -> int:
    """Fields a k-step block reads or writes: 2S+2 reads, 2S writes."""
    return 4 * shots + 2


def block_bytes(nz: int, nx: int, shots: int) -> int:
    """Least HBM bytes of one k-step block over an ``nz × nx`` grid."""
    return block_fields(shots) * nz * nx * F32


def stripe_block_bytes(nz: int, nx: int, shots: int,
                       stripes: int) -> list[int]:
    """Least HBM bytes of one k-step block on each of ``stripes``
    column stripes (the striped domain cuts the width into equal
    parts; halo columns are not counted)."""
    if nx % stripes:
        raise ValueError(f"{nx} columns do not split into {stripes} stripes")
    return [block_bytes(nz, nx // stripes, shots)] * stripes


def block_ops(nz: int, nx: int, shots: int, k: int) -> int:
    """float32 operations of one k-step block."""
    return OPS_PER_POINT * shots * nz * nx * k


def ops_per_byte(shots: int, k: int) -> float:
    """Arithmetic intensity of a k-step block at the least traffic."""
    return OPS_PER_POINT * shots * k / (block_fields(shots) * F32)


def peak(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``.  A kind that
    is not in ``peaks.json`` is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name} (known: {sorted(table)})")
    return table[device_kind]
