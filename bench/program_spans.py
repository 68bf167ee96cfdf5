"""The program's own spans (``repro.core.spans``) inside a run's window.

The program records a span at each boundary of the orchestrator
(``orch.*``) and of the FWI session (``fwi.*``), on the host clock.  A
metric reads those of the window here and maps host times onto the
trace with ``Run.to_trace``.  A program that records no spans (one
older than ``repro.core.spans``) reads as None, and so does every metric
that needs them.
"""
from __future__ import annotations


def in_window(run) -> list | None:
    """The program's spans that lie within the run's window, or None
    where the program records none."""
    try:
        from repro.core import spans
    except ImportError:
        return None
    return spans.recorded(*run.window)


def named(run, name: str) -> list:
    """The window's spans called ``name`` (none without the module)."""
    return [s for s in in_window(run) or () if s.name == name]


def mean_ms(run, name: str) -> float | None:
    """Mean duration of the window's ``name`` spans in milliseconds;
    None where there are none."""
    found = named(run, name)
    if not found:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in found) / len(found)
