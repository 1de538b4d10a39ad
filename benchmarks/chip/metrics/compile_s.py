"""Seconds of XLA compile or compile-cache load in the process so far:
the program's ``xla.compile_s`` counters in its process registry, read
after the window (the program's compiles of set-up, and any inside the
window)."""


def read(run):
    from repro.obs.metrics import default_registry
    rows = [c for c in default_registry().counters()
            if c.name == "xla.compile_s"]
    return sum(c.value for c in rows) if rows else None
