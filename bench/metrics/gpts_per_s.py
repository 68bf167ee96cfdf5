"""gpts_per_s: billions of grid-point updates per second of the
window, in the cells of the 4096-square grid (host clock)."""
from bench.readers import gpts_per_s as read  # noqa: F401
