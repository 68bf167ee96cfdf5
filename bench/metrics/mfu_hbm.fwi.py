"""mfu_hbm.fwi: the whole step's share of the chip's HBM peak in the
cells of the 4096-square grid (profiler trace, work from shapes);
moves gpts_per_s."""
from bench.readers import mfu_hbm as read  # noqa: F401
