"""Whole-step share of the peak while the device is busy: frames done in
the traced window times the model FLOPs of a frame, over peak FLOP/s x
the device's busy seconds (summed over the chips).  At a fixed offered
rate the plain ratio over the window would not move, so this one divides
by busy time."""

from chipbench import counts


def read(run):
    t = run.trace
    if t is None or run.peak is None or not run.frames_in_window:
        return None
    busy = sum(t.busy_s)
    if busy <= 0:
        return None
    flops = run.frames_in_window * counts.frame_flops(run.cell.layers)
    return 100.0 * flops / (run.peak["flops_per_s"] * busy)
