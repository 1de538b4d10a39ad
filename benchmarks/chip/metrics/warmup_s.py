"""Host wall from the first call (or ``fleet.start()``) until the path
is warm: the entry's warm-up calls, each waited for on the host."""

import math


def read(run):
    return None if math.isnan(run.warmup_s) else run.warmup_s
