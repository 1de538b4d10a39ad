"""Median over the window's frames of worker ``w0``'s ``worker.d2h``
span: the blocking ``np.asarray`` of the stage's outputs, which waits
for the device and copies back (``chipbench.spans``)."""

from chipbench import spans


def read(run):
    return spans.median_ms(spans.per_frame(run, {"worker.d2h"}, "dist:w0"))
