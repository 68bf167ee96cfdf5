"""idle_share.fwi: idle share of the held devices in the cells of the
4096-square grid (profiler trace); moves gpts_per_s."""
from bench.readers import idle_share as read  # noqa: F401
