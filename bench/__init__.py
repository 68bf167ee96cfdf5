"""The chip benchmark: the harness behind ``bench/run.py``, and the
yardstick it measures with (traffic, reference, work counts, peaks,
trace reduction).  ``BENCHMARK.json`` at the repository root names its
cells; each configuration, traffic mix, check and metric is a file of
its own under this directory, found by name."""
