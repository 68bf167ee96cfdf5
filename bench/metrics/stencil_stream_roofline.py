"""stencil_stream_roofline: the streamed shot-batched stencil kernel's
share of its HBM roofline (profiler trace, work from shapes)."""
import re

from bench.readers import roofline

KERNEL = re.compile(r"^wave_block_shots_stream_pallas")


def read(run):
    return roofline(run, KERNEL)
