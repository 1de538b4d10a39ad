"""Median over the window's frames of worker ``w0``'s ``worker.h2d``
span: ``jax.device_put`` of the frame's inputs (``chipbench.spans``)."""

from chipbench import spans


def read(run):
    return spans.median_ms(spans.per_frame(run, {"worker.h2d"}, "dist:w0"))
