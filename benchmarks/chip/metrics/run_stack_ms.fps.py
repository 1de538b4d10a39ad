"""Median over the window's ``Deployment.run`` calls of the call's
``run.stack`` span: ``jnp.stack`` of the frame list, with the host to
device copies it starts (``chipbench.spans``)."""

from chipbench import spans


def read(run):
    return spans.median_ms(spans.per_call(run, "run.stack"))
