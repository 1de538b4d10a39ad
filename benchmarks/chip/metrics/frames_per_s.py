"""Frames whose logits reached the client within the window, over the
window's seconds: one ratio over the whole window."""


def read(run):
    if run.window_s > 0 and run.frames_in_window:
        return run.frames_in_window / run.window_s
    return None
