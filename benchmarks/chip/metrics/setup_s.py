"""Process start to the first instant of the window: imports, device
start, plan, weights, compile or cache load, warm-up, fleet start."""

import math


def read(run):
    return None if math.isnan(run.setup_s) else run.setup_s
