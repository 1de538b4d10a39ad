"""Median over the window's ``Deployment.run`` calls of the call's
``stage`` spans summed: each stage's dispatch on the host
(``chipbench.spans``)."""

from chipbench import spans


def read(run):
    return spans.median_ms(spans.per_call(run, "stage"))
