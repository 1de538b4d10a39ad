"""XLA compile accounting from JAX's own monitoring events.

JAX reports every backend compile — a real XLA compile or a load from
the persistent compilation cache — as one
``/jax/core/compile/backend_compile_duration`` time span carrying the
compiled function's ``fun_name``.  :func:`install` registers one
listener for it per process; each event then

* adds to the process registry's counters ``xla.compiles{fun=...}``
  and ``xla.compile_s{fun=...}``;
* emits a ``compile`` span with a ``fun`` attribute into the active
  tracer (:func:`repro.obs.trace.current`), on its ``perf_counter``
  timeline.

A compile inside a served window therefore shows up as a count, and as
a span wherever a tracer was active.
"""

from __future__ import annotations

import threading
import time

from . import trace as obs_trace
from .metrics import default_registry

#: The JAX monitoring event of one backend compile or cache load.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_LOCK = threading.Lock()
_installed = False


def install() -> None:
    """Register the compile listener (once per process)."""
    global _installed
    with _LOCK:
        if _installed:
            return
        import jax.monitoring
        jax.monitoring.register_event_time_span_listener(_on_time_span)
        _installed = True


def _on_time_span(event: str, start: float, end: float, **kw) -> None:
    if event != COMPILE_EVENT:
        return
    end_pc = time.perf_counter()
    dur = max(0.0, end - start)
    fun = str(kw.get("fun_name", "?"))
    reg = default_registry()
    with _LOCK:                 # worker threads may compile at once
        reg.counter("xla.compiles", fun=fun).inc()
        reg.counter("xla.compile_s", fun=fun).inc(dur)
    tr = obs_trace.current()
    if tr:
        tr.emit("compile", end_pc - dur - tr.epoch, dur, fun=fun)
