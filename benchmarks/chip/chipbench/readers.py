"""Bodies of the per-layer metric readers that several metrics share:
the ``.fps`` and ``.p50`` variants of a metric read the same number in
cells that report different end-to-end metrics."""

from __future__ import annotations


def idle_share(run):
    """Per cent of the traced window in which no op ran on the device,
    averaged over the cell's chips."""
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
