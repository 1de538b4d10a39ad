"""Median over every frame due in the window, each from its due time to
its logits on the host (nearest rank)."""

from chipbench.stats import quantile


def read(run):
    if not run.latencies_s:
        return None
    return quantile(run.latencies_s, 50) * 1e3
