"""stream_read_ratio.fwi: HBM bytes the streamed stencil kernel moves
per k-step block over the least bytes of the block (``work.block_bytes``
of the logical grid), from the tiling the program reports on its
``fwi.remesh`` span (host clock, counted from shapes); moves gpts_per_s.

Per stripe, each of the ``rows / bz`` strips of each of the
``n_shots / shot_tile`` shot tiles reads ``2·shot_tile + 2`` windows of
``win`` rows (the tile's p and p_prev, and the two model fields) and
writes ``2·shot_tile`` strips of ``bz`` rows, every row ``lanes`` float32
wide: halo rows, shared model fields read again per tile, and padding
all count.  Sessions are weighted by the steps their ``fwi.dispatch``
spans advanced.  None on a program whose ``fwi.remesh`` lacks the tiling
or whose interior does not stream."""
from bench import work
from bench.program_spans import named


def kernel_bytes(a: dict, n_shots: int) -> int:
    """Bytes the streamed kernel moves in one k-step block on every
    stripe of the session that ``a`` (its remesh attributes) describes."""
    s, bz, win = a["shot_tile"], a["bz"], a["win"]
    strips = a["stripes"] * (n_shots // s) * (a["rows"] // bz)
    return strips * ((2 * s + 2) * win + 2 * s * bz) * a["lanes"] * work.F32


def read(run):
    steps: dict[int, int] = {}
    for s in named(run, "fwi.dispatch"):
        steps[s.attrs["session"]] = \
            steps.get(s.attrs["session"], 0) + s.attrs["steps"]
    tiled = {s.attrs["session"]: s.attrs for s in named(run, "fwi.remesh")
             if s.attrs.get("stream") and s.attrs["session"] in steps}
    total = sum(steps[k] for k in tiled)
    if not total:
        return None
    f = run.fwi
    least = work.block_bytes(f["nz"], f["nx"], f["n_shots"])
    return sum(kernel_bytes(a, f["n_shots"]) * steps[k]
               for k, a in tiled.items()) / least / total
