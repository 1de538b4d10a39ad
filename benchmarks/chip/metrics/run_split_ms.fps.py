"""Median over the window's ``Deployment.run`` calls of the call's
``run.split`` span: slicing the stacked sinks into per-frame dicts
(``chipbench.spans``)."""

from chipbench import spans


def read(run):
    return spans.median_ms(spans.per_call(run, "run.split"))
