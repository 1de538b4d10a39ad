"""Whole-step share of the chips' peak: frames done in the traced window
times the model FLOPs of a frame, over chips x peak FLOP/s x window."""

from chipbench import counts


def read(run):
    t = run.trace
    if t is None or run.peak is None or not run.frames_in_window:
        return None
    flops = run.frames_in_window * counts.frame_flops(run.cell.layers)
    return 100.0 * flops / (t.chips * run.peak["flops_per_s"] * t.window_s)
