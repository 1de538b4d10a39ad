"""Median over the window's frames of the frame's ``link.wait`` spans
summed: from the sender's stamp to the receiver taking the message, on
the feed link, between stages and on the sink link
(``chipbench.spans``)."""

from chipbench import spans


def read(run):
    return spans.median_ms(spans.per_frame(run, {"link.wait"}))
