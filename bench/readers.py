"""Reductions that several metrics share: a metric file under
``metrics/`` names one of these for the cells it lists, so that a
quantity split by the end-to-end metric it moves is computed once."""
from __future__ import annotations

from bench import trace as tr
from bench import work


def gpts_per_s(run) -> float:
    """Grid-point updates in the window (shots x nz x nx x timesteps
    advanced, over every stripe and session) per second of the window's
    wall time, transitions and job starts included, in billions."""
    return run.points() / run.window_s / 1e9


def idle_share(run) -> float | None:
    """Share of the device time the job held in the window in which no
    operation ran.  A device counts as held while a session whose mesh
    includes it is live, so the chips a one-stripe phase leaves unused
    do not count as idle."""
    if run.trace is None:
        return None
    held = run.held()
    total = sum(tr.total(v) for v in held.values())
    busy = sum(tr.total(tr.intersect(run.trace.busy(d), v))
               for d, v in held.items())
    return 100.0 * (1.0 - busy / total)


def mfu_hbm(run) -> float | None:
    """The whole step's share of the chip's peak.  The step is
    HBM-bound, so the peak is bandwidth: the least HBM bytes of the
    window's timesteps ((4S+2) fields of nz x nx x 4 B per k-step block,
    from shapes, at the cell's exchange interval) over the peak
    bandwidth times the chip-seconds the job held."""
    if run.trace is None:
        return None
    chip_s = sum(tr.total(v) for v in run.held().values()) / 1e9
    bw = work.peak(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * run.least_bytes() / (bw * chip_s)


def roofline(run, kernel) -> float | None:
    """A stencil kernel's share of its roofline.  The stencil is
    HBM-bound (under 5 operations per byte, and no float32 vector peak
    is published to bound it by compute): the least HBM bytes of the
    window's timesteps over the peak bandwidth, divided by the summed
    device time of the events whose instruction name ``kernel`` (a
    compiled pattern) matches.  None where the kernel did not run."""
    if run.trace is None:
        return None
    secs = run.trace.op_seconds(kernel)
    if secs <= 0:
        return None
    bw = work.peak(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * run.least_bytes() / bw / secs
