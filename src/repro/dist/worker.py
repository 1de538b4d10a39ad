"""Distributed stage worker: one pipeline stage, one persistent loop.

A worker never sees live Python objects from the launcher: its whole
configuration is one JSON *worker payload* — the versioned Deployment
artifact (plan + specs + model graph + CostTable, exactly what
``Deployment.save`` writes) plus this worker's stage index and link
roles.  Rebuilding from the artifact is the hand-off contract:
``Deployment.from_json`` re-installs the CostTable's autotuned kernel
winners process-wide (the executable-cache warmup), and model weights
are re-initialized deterministically from the payload seed, so every
worker — thread or spawned process — holds bit-identical state.

The loop is ``recv -> StageExecutor compiled segment -> send``:
micro-batched messages go through the ``lax.scan`` ``run_frames`` path,
heartbeats are emitted on the control link between frames, and a
``stop`` received from upstream is forwarded downstream *after* all
data messages (links are FIFO), which is what makes the launcher's
drain lossless.  ``die`` simulates a crash: the worker exits silently
— no stop forwarded, no stats, links left dangling — so peer-timeout
detection can be drilled.

With ``trace`` on, the worker records its spans into a
:class:`~repro.obs.trace.Tracer` on the launcher tracer's ``epoch``
(one ``perf_counter`` timeline): per frame ``stage.compute`` (the
interval ``compute_s`` sums) holding ``worker.h2d``, the executor's
``stage`` dispatch and ``worker.d2h``; then ``worker.send``; and the
links' ``link.wait``/``link.decode``/``link.encode``.  Its bounded
rings travel back in the ``stats`` message.
"""

from __future__ import annotations

import json
import time
import traceback

import numpy as np

from ..obs.trace import NULL_TRACER, Tracer
from .transport import Message, TCPListener, TCPTransport


def build_payload(deployment_json: str, stage: int, *, worker: str,
                  devices: list[str], recv_nodes: list[str],
                  recv_image: bool, forward: list[str], forward_image: bool,
                  last: bool, seed: int, heartbeat_s: float,
                  start_timeout_s: float, chunk_bytes: int,
                  epoch: float, trace: bool) -> dict:
    """The JSON-safe worker payload (see module docstring)."""
    return {"deployment": deployment_json, "stage": stage, "worker": worker,
            "devices": list(devices), "recv_nodes": list(recv_nodes),
            "recv_image": bool(recv_image), "forward": list(forward),
            "forward_image": bool(forward_image), "last": bool(last),
            "seed": int(seed), "heartbeat_s": float(heartbeat_s),
            "start_timeout_s": float(start_timeout_s),
            "chunk_bytes": int(chunk_bytes),
            "epoch": float(epoch), "trace": bool(trace)}


class StageWorker:
    """Persistent stage loop over abstract transports (thread or
    process substrate — the code path is identical)."""

    def __init__(self, payload: dict, upstream, downstream,
                 control_out, control_in=None):
        self.payload = payload
        self.upstream = upstream
        self.downstream = downstream
        self.control_out = control_out
        self.control_in = control_in
        self.name = payload["worker"]
        self.stage_index = payload["stage"]
        self.frames = 0
        self.compute_s = 0.0
        self.tracer = (Tracer(epoch=payload["epoch"]) if payload["trace"]
                       else NULL_TRACER)
        self._silent = False          # die received: simulate a crash

    # -- lifecycle -------------------------------------------------------
    def run(self) -> None:
        try:
            self._setup()
            self._send_ctrl("ready")
            self._loop()
        except ConnectionError as e:
            self._send_error(f"link failure: {e}")
        except Exception:
            self._send_error(traceback.format_exc())
        finally:
            if not self._silent:
                self._close()

    def _setup(self) -> None:
        import jax

        from ..api.deployment import Deployment
        from ..pipeline.stage import StageExecutor

        p = self.payload
        # the artifact round-trip IS the hand-off: from_json re-applies
        # the exec-spec cache bound and installs the shipped CostTable's
        # autotuned kernel winners (per-worker executable warmup)
        dep = Deployment.from_json(p["deployment"])
        st = dep.pico.pipeline.stages[self.stage_index]
        spec = dep.exec_spec
        # built exactly the way PipelineRunner builds its executors
        # (backend/mode only), so the executable-cache key — and the
        # numerics — match the single-process compiled path bit-for-bit
        self.executor = StageExecutor(
            dep.model, st.nodes, list(st.fractions),
            name=f"stage{self.stage_index}", backend=spec.backend,
            mode=spec.mode, tracer=self.tracer)
        self.upstream.tracer = self.downstream.tracer = self.tracer
        # stage i runs on local device i (mod the count): thread workers
        # of one process spread over the host's chips; committed params
        # and inputs make the stage's executable run there
        devices = jax.local_devices()
        self.device = devices[self.stage_index % len(devices)]
        self.params = jax.device_put(
            dep.model.init(jax.random.PRNGKey(p["seed"])), self.device)
        self.heartbeat_s = p["heartbeat_s"]
        self.forward = list(p["forward"])
        self.forward_image = p["forward_image"]
        self.last = p["last"]
        self._last_hb = 0.0

    def _loop(self) -> None:
        while True:
            self._heartbeat()
            if self._poll_control():
                return                          # die: simulated crash
            msg = self.upstream.recv(timeout=self.heartbeat_s)
            if msg is None:
                continue
            if msg.kind == "stop":
                # FIFO links: every data message is already behind us,
                # so forwarding stop completes the lossless drain
                self.downstream.send(msg)
                self._send_stats()
                return
            if msg.kind == "frame":
                self._frame(msg)

    def _frame(self, msg: Message) -> None:
        import jax

        tr = self.tracer
        fid = msg.fids[0]
        produced = {k: v for k, v in msg.tensors.items()
                    if k != "__image__"}
        image = msg.tensors.get("__image__")
        t0 = time.perf_counter()
        with tr.wall_span("stage.compute", stage=self.stage_index,
                          worker=self.name, frames=len(msg.fids), fid=fid):
            with tr.wall_span("worker.h2d", fid=fid):
                args = jax.device_put((produced, image), self.device)
            if len(msg.fids) > 1:
                outs = self.executor.run_frames(self.params, *args)
            else:
                outs = self.executor(self.params, *args)
            with tr.wall_span("worker.d2h", fid=fid):
                outs = {k: np.asarray(v) for k, v in outs.items()}  # blocks
        dt = time.perf_counter() - t0
        if not msg.meta.get("warmup"):
            # the probe's wall is dominated by the stage compile — keep
            # it out of the steady-state compute stats validate() rates
            self.frames += len(msg.fids)
            self.compute_s += dt
        with tr.wall_span("worker.send", fid=fid):
            avail = dict(produced)
            avail.update(outs)
            out = {n: avail[n] for n in self.forward}
            if self.forward_image:
                out["__image__"] = image
            self.downstream.send(Message("result" if self.last else "frame",
                                         msg.fids, out, msg.meta))

    # -- control ---------------------------------------------------------
    def _heartbeat(self) -> None:
        now = time.monotonic()
        if now - self._last_hb >= self.heartbeat_s:
            self._last_hb = now
            self._send_ctrl("heartbeat")

    def _poll_control(self) -> bool:
        if self.control_in is None:
            return False
        try:
            msg = self.control_in.recv(timeout=0.0)
        except ConnectionError:
            return False
        if msg is not None and msg.kind == "die":
            self._silent = True
            return True
        return False

    def _send_ctrl(self, kind: str, **meta) -> None:
        meta.setdefault("worker", self.name)
        meta.setdefault("stage", self.stage_index)
        try:
            self.control_out.send(Message(kind, meta=meta))
        except (ConnectionError, OSError):
            pass                    # launcher gone: nothing to tell

    def _send_stats(self) -> None:
        tr = self.tracer
        spans = tr.rows() if tr else []
        evicted = tr.evicted if tr else 0
        self._send_ctrl(
            "stats", frames=self.frames, compute_s=self.compute_s,
            device_id=self.device.id, bytes_in=self.upstream.bytes_recv,
            bytes_out=self.downstream.bytes_sent,
            send_s=self.downstream.send_s, spans=spans, evicted=evicted,
            evicted_until=tr.evicted_until if evicted else None)

    def _send_error(self, detail: str) -> None:
        self._send_ctrl("error", detail=detail, frames=self.frames)

    def _close(self) -> None:
        for t in (self.upstream, self.downstream):
            try:
                t.close()
            except Exception:
                pass


def worker_main(payload_path: str, control_host: str,
                control_port: int) -> None:
    """Spawned-process entry point: handshake over the control link,
    wire up the data links, then run the stage loop.

    Protocol: bind an ephemeral data listener -> connect the control
    socket -> ``hello`` (carrying the data port) -> receive ``wire``
    (the downstream address) -> connect downstream -> accept upstream
    -> :meth:`StageWorker.run`.
    """
    with open(payload_path) as f:
        payload = json.load(f)
    chunk = payload["chunk_bytes"]
    start_timeout = payload["start_timeout_s"]
    name = payload["worker"]
    listener = TCPListener()
    control = TCPTransport.connect((control_host, control_port),
                                   link=f"ctrl:{name}", chunk_bytes=chunk,
                                   timeout=start_timeout)
    control.send(Message("hello", meta={"worker": name,
                                        "stage": payload["stage"],
                                        "data_port": listener.port}))
    wire = control.recv(timeout=start_timeout)
    if wire is None or wire.kind != "wire":
        raise TimeoutError(f"worker {name}: no wiring from launcher")
    host, port = wire.meta["downstream"]
    downstream = TCPTransport.connect((host, int(port)),
                                      link=wire.meta["link_out"],
                                      chunk_bytes=chunk,
                                      timeout=start_timeout)
    upstream = listener.accept(link=wire.meta["link_in"], chunk_bytes=chunk,
                               timeout=start_timeout)
    listener.close()
    StageWorker(payload, upstream, downstream, control, control).run()
