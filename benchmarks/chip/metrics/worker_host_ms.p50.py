"""Host milliseconds per frame of worker ``w0``: its ``compute_s`` over
its frames.  ``compute_s`` is host wall time around ``device_put``, the
stage call and the blocking copy back, not device time."""


def read(run):
    rep = run.dist_report
    st = rep.worker_stats.get("w0", {}) if rep is not None else {}
    if not st.get("frames"):
        return None
    return st["compute_s"] / st["frames"] * 1e3
