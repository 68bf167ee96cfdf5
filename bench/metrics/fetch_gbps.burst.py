"""fetch_gbps.burst: the checkpoint's device-to-host rate, the bytes of
the program's ``fwi.fetch`` spans (one per wavefield, counted from its
shape) over their summed duration, in 1e9 B/s (host clock); moves
burst_s.  None without a fetch."""
from bench.program_spans import named


def read(run):
    fetches = named(run, "fwi.fetch")
    secs = sum(s.t1 - s.t0 for s in fetches)
    if secs <= 0:
        return None
    return sum(s.attrs["bytes"] for s in fetches) / secs / 1e9
