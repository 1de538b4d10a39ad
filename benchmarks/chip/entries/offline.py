"""Entry ``offline``: ``Deployment.run(list_of_frames)``, closed loop.

One client calls ``dep.run`` with a list of ``frames_per_call`` frames
and fetches every frame's logits to the host before its next call.  The
lists come from a pool of ``stacks`` distinct stacks made from the seed
at set-up and cycled, so frame generation is never timed.  The window
ends with the first call that returns after ``--seconds``: the rate is
all the frames of all its calls over all its time.

The weights are ``model.init(PRNGKey(k))`` with the benchmark's non-zero
biases in place of its zero ones, made on the device in one jitted call
(``bench.program_params``); the reference draws its own from the same
keys.  The check compares whole calls, every slot of the scan.
"""

from __future__ import annotations

import time

import jax

from chipbench import bench


def run(r: bench.Run) -> None:
    t = r.cell.traffic
    per, stacks = t["frames_per_call"], t["stacks"]
    dep, model = bench.deploy(r)
    params = bench.program_params(r, model)
    r.group = per
    pool = bench.frames(r, per * stacks)
    calls = [list(pool[k * per:(k + 1) * per]) for k in range(stacks)]

    def call(k):
        with jax.profiler.TraceAnnotation("chipbench.call"):
            outs = dep.run(calls[k], params=params)
        with jax.profiler.TraceAnnotation("chipbench.fetch"):
            return jax.device_get(outs)

    t0 = time.perf_counter()
    for i in range(t["warmup_calls"]):
        call(i % stacks)
    r.warmup_s = time.perf_counter() - t0
    r.mark("warm-up")

    got = []
    with bench.window(r):
        t0 = time.perf_counter()
        while True:
            got.append(call(len(got) % stacks))
            if time.perf_counter() - t0 >= r.seconds:
                break
        r.window_s = time.perf_counter() - t0
    r.frames_in_window = r.attempted = len(got) * per
    for c, outs in enumerate(got):
        for j, out in enumerate(outs):
            (logits,) = out.values()
            r.outputs[c * per + j] = logits.reshape(-1)
            r.frame_of[c * per + j] = (c % stacks) * per + j
    r.note(f"offline: {len(got)} calls of {per} frames in "
           f"{r.window_s:.3f} s")
