"""Median over the window's frames of the frame's ``link.encode`` and
``link.decode`` spans summed: the wire codec on every link it crosses
(``chipbench.spans``)."""

from chipbench import spans


def read(run):
    return spans.median_ms(spans.per_frame(
        run, {"link.encode", "link.decode"}))
