"""``repro.compile(model, cluster) -> Deployment`` — the public facade.

One object owns the whole plan → calibrate → execute lifecycle that the
paper splits into an offline optimizer and an online executor:

    dep = repro.compile(model, cluster, plan_spec, exec_spec)
    dep.run(frames)                  # bit-exact pipelined inference
    dep.runtime(deploy_spec)         # event-driven cluster runtime
    dep.server(streaming=True)       # serving front-end
    dep.scheduler(tenants=[...])     # multi-tenant co-hosting
    dep.save("plan.json")            # durable, versioned artifact
    dep2 = repro.Deployment.load("plan.json")   # no re-plan, no re-calib

``save``/``load`` round-trips are exact: the loaded deployment's
``simulate()`` report and per-frame outputs are bit-identical to the
original, and neither the planner nor the calibrator runs on load —
the offline plan ships to the fleet as data.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.cost import Cluster, CostTable
from ..core.planner import PicoPlan, plan_with_spec
from ..obs import compiles
from ..obs import trace as obs_trace
from ..obs.metrics import MetricsRegistry, default_registry
from ..obs.trace import Tracer
from . import artifacts
from .specs import DeploySpec, ExecSpec, PlanSpec


def compile(model, cluster: Cluster,
            plan_spec: PlanSpec | None = None,
            exec_spec: ExecSpec | None = None, *,
            cost_table: CostTable | None = None,
            params=None, key=None) -> "Deployment":
    """Plan (and optionally calibrate) ``model`` on ``cluster``.

    ``model`` is a graph carrier (:class:`~repro.models.cnn.builder.CNNDef`
    or anything with ``.graph``/``.input_size``).  With
    ``exec_spec.calibrate`` every stage of the initial plan is timed
    through its compiled executable and the plan is re-built on the
    measured :class:`CostTable` (piece chain reused).  ``params``/``key``
    seed the model weights for calibration and later ``run()`` calls;
    ``cost_table`` supplies a previously measured table up front.
    """
    plan_spec = plan_spec or PlanSpec()
    exec_spec = exec_spec or ExecSpec()
    compiles.install()          # calibration compiles count too
    if params is None and key is not None:
        params = _init_params(model, key)
    # the deployment's tracer captures its whole lifecycle: the offline
    # plan (and calibration) spans land here, and later traced runtime
    # runs append to the same timeline
    tracer = Tracer()
    with obs_trace.scoped(tracer):
        if exec_spec.autotune:
            # tune kernel blocks first so calibration (and with it the
            # planner's cost ratios) measures the tuned kernels; winners
            # merge into the same CostTable artifact as the ratios
            from ..exec.autotune import autotune_model
            cost_table, _ = autotune_model(
                model,
                backend=exec_spec.backend
                or getattr(model, "backend", None) or "pallas",
                table=cost_table, iters=exec_spec.autotune_iters)
        # one PlannerCache for the deployment's lifetime: the post-
        # calibration re-plan and any later .replan() hops reuse the
        # initial plan's segment geometry (incremental hot path)
        from ..core.pipeline_dp import PlannerCache
        cache = PlannerCache()
        pico = plan_with_spec(model.graph, cluster, model.input_size,
                              plan_spec, cost_table=cost_table,
                              planner_cache=cache)
        if exec_spec.calibrate:
            from ..exec.calibrate import calibrate_plan
            if params is None:
                params = _init_params(model, key)
            report = calibrate_plan(model, params, pico.pipeline.stages,
                                    spec=exec_spec,
                                    iters=exec_spec.calibrate_iters)
            tuned = cost_table.kernels if cost_table is not None else {}
            cost_table = report.table()
            cost_table.kernels.update(tuned)  # ratios + tunings, one store
            pico = plan_with_spec(model.graph, cluster, model.input_size,
                                  plan_spec, partition=pico.partition,
                                  cost_table=cost_table,
                                  planner_cache=cache)
    dep = Deployment(model, cluster, plan_spec, exec_spec, pico,
                     cost_table=cost_table, params=params, tracer=tracer)
    dep._planner_cache = cache
    return dep


def _init_params(model, key=None):
    return model.init(key if key is not None else jax.random.PRNGKey(0))


#: frames per chunk of a host-fed scan.  ``Deployment.run`` dispatches
#: chunk k's scan before it copies chunk k+1 in, so only the first
#: chunk's copy leaves the device idle; each chunk costs one more stack
#: and one more scan dispatch on the host.  On a TPU v5e, 32-frame calls
#: of 224x224 frames ran at 1820 / 1990 / 1870 frames/s (VGG16) and
#: 2290 / 2250 / 1820 (ResNet34) in chunks of 16 / 8 / 4, against 1590
#: and 1910 in one stack: smaller chunks hide more of the copy, but
#: their host work outgrows what a short scan can cover.  Fixed in
#: frames, so a call compiles at most two scan lengths (the chunk and a
#: ragged tail).
FEED_CHUNK = 8

#: one dispatch each, compiled once per frame count and shape
_stack = jax.jit(jnp.stack)
_unstack = jax.jit(lambda parts: {k: [r for p in parts for r in
                                      jnp.unstack(p[k])]
                                  for k in parts[0]})


def stack_frames(frames: Sequence) -> tuple[jax.Array, str]:
    """``(stack, src)``: ``frames`` stacked on a new leading axis as one
    device array with ``jnp.stack``'s dtype, in one jitted dispatch.
    Frames that are all NumPy arrays first cross to the device in one
    batched ``device_put`` (``src="host"``); any other sequence goes in
    as it is (``src="device"``).  Per-frame buffers in one call beat one
    host-stacked buffer on a TPU v5e: 6.6 ms against 9.7 ms for 32
    frames of 224x224x3 float32, 62 ms against 281 ms while the profiler
    records.  ``Deployment.run`` calls it once per chunk of
    :func:`feed_chunks`."""
    if all(isinstance(x, np.ndarray) for x in frames):
        return _stack(jax.device_put(frames)), "host"
    return _stack(frames), "device"


def feed_chunks(frames: list) -> list[list]:
    """``frames`` cut into the chunks ``Deployment.run`` stacks and scans
    one after another: chunks of :data:`FEED_CHUNK` (the last one
    ragged) where every frame is a NumPy array of one shape and dtype,
    so that each chunk's copy in runs under the previous chunk's scan.
    Otherwise one chunk: device frames have no copy to hide, and frames
    of mixed shapes or dtypes keep ``jnp.stack``'s promotion (or error)
    over the whole list."""
    if len(frames) > FEED_CHUNK and \
            all(isinstance(x, np.ndarray) for x in frames) and \
            len({(x.shape, x.dtype) for x in frames}) == 1:
        return [frames[i:i + FEED_CHUNK]
                for i in range(0, len(frames), FEED_CHUNK)]
    return [frames]


@dataclass
class Deployment:
    """A planned (and optionally calibrated) pipeline, ready to execute,
    serve, re-plan, or ship as a JSON artifact."""

    model: object
    cluster: Cluster
    plan_spec: PlanSpec
    exec_spec: ExecSpec
    pico: PicoPlan
    cost_table: CostTable | None = None
    params: object = field(default=None, repr=False, compare=False)
    _runner: object = field(default=None, repr=False, compare=False)
    #: span sink for the deployment lifecycle — plan/calibrate spans
    #: from :func:`compile`, plus every runtime run started with
    #: ``DeploySpec(trace=True)``.  Export with ``tracer.save(path)``.
    tracer: object = field(default=None, repr=False, compare=False)
    #: deployment-scoped metrics registry; runtime runs with
    #: ``DeploySpec(metrics=True)`` (the default) publish here.
    metrics: object = field(default=None, repr=False, compare=False)

    def __post_init__(self):
        # the executable-cache bound is process-global; a deployment
        # carrying one applies it the same way on compile and on load
        self.exec_spec.apply_cache_limit()
        # autotuned kernel winners ride in the cost table; install them
        # process-wide so a loaded artifact re-arms the fast path with
        # zero re-tuning (same compile/load symmetry as the cache bound)
        if self.cost_table is not None and \
                getattr(self.cost_table, "kernels", None):
            from ..exec.autotune import install
            install(self.cost_table.kernels)
        if self.tracer is None:
            self.tracer = Tracer()
        if self.metrics is None:
            self.metrics = MetricsRegistry()
        self._calls = 0
        compiles.install()

    # ---------------- plan views ----------------

    @property
    def pipeline(self):
        return self.pico.pipeline

    @property
    def partition(self):
        return self.pico.partition

    @property
    def period(self) -> float:
        return self.pico.period

    @property
    def latency(self) -> float:
        return self.pico.latency

    @property
    def throughput(self) -> float:
        return self.pico.throughput

    def describe(self) -> str:
        """One-paragraph human summary (CLI/report helper)."""
        st = self.pico.pipeline.stages
        lines = [f"{getattr(self.model, 'name', 'model')}: "
                 f"{len(self.pico.partition.pieces)} pieces -> "
                 f"{len(st)} stages on {len(self.cluster)} devices; "
                 f"period {self.period * 1e3:.2f} ms "
                 f"({60.0 / self.period:.1f} frames/min), "
                 f"latency {self.latency * 1e3:.2f} ms"]
        for s in st:
            lines.append(
                f"  stage pieces {s.first_piece}-{s.last_piece} on "
                f"{[d.name for d in s.devices]}  "
                f"T={s.cost.total * 1e3:.2f} ms")
        if self.cost_table is not None:
            lines.append(f"  calibrated: {len(self.cost_table)} segment "
                         f"ratio(s)")
            if self.cost_table.kernels:
                lines.append(f"  autotuned: {len(self.cost_table.kernels)} "
                             f"kernel shape(s)")
        return "\n".join(lines)

    # ---------------- execution ----------------

    def load_params(self, key=None) -> "Deployment":
        """Initialize model weights (idempotent unless ``key`` given)."""
        if self.params is None or key is not None:
            self.params = _init_params(self.model, key)
            self._runner = None
        return self

    @property
    def runner(self):
        """Lazy :class:`~repro.pipeline.runner.PipelineRunner` over the
        plan's stages (compiled per ``exec_spec``)."""
        if self._runner is None:
            from ..pipeline.runner import PipelineRunner
            self._runner = PipelineRunner(self.model, self.pico.pipeline,
                                          exec_spec=self.exec_spec)
        return self._runner

    def run(self, frames, params=None):
        """Execute frame(s) through the pipelined stages (bit-exact with
        the monolithic forward).  A single array returns one sink dict;
        a sequence returns a list of sink dicts of device arrays.
        A single array or a one-frame sequence takes the runner's
        per-frame ``__call__``; longer sequences go through the
        compiled ``lax.scan`` ``run_frames`` path (one dispatch per
        stage and chunk).  Nothing here waits for a result.

        The scan path feeds the frames in the chunks of
        :func:`feed_chunks`: NumPy frames in chunks of
        :data:`FEED_CHUNK`, each stacked onto the device and sent
        through every stage before the next chunk is copied in, so the
        copy of chunk k+1 runs under the scan of chunk k; any other
        sequence as one chunk.  Per frame the scan step is the same
        whatever the chunking.

        Each call records on :attr:`tracer` a ``run`` span (``call``,
        ``frames``, and on the scan path ``chunks``) holding one
        ``stage`` span per stage dispatch and, on the scan path, per
        chunk a ``run.stack`` (``chunk``: its index) before that chunk's
        stages, then one ``run.split`` after them all; the tracer is
        active for the call, so its compiles land there too.
        ``run.stack`` puts a chunk on the device as one stacked array
        (:func:`stack_frames`); its ``src`` is ``host`` when every frame
        is a NumPy array (one batched transfer, then one jitted stack)
        and ``device`` otherwise (one jitted stack).  ``run.split``
        unstacks every chunk's sinks into per-frame arrays in one jitted
        call; its ``dispatches`` counts those calls.  The process
        registry's ``run.feed_chunks`` counter counts the chunks fed."""
        if params is None:
            params = self.load_params().params
        self._calls += 1
        call = self._calls
        tr = self.tracer
        with obs_trace.scoped(tr), tr.wall_span("run", call=call) as span:
            if hasattr(frames, "ndim"):
                span.set(frames=1)
                return self.runner(params, frames)
            frames = list(frames)
            span.set(frames=len(frames))
            if len(frames) > 1:
                chunks = feed_chunks(frames)
                span.set(chunks=len(chunks))
                default_registry().counter("run.feed_chunks").inc(
                    len(chunks))
                parts = []
                for i, chunk in enumerate(chunks):
                    with tr.wall_span("run.stack", call=call,
                                      chunk=i) as st:
                        stacked, src = stack_frames(chunk)
                        st.set(src=src)
                    parts.append(self.runner.run_frames(params, stacked))
                with tr.wall_span("run.split", call=call) as sp:
                    rows = _unstack(parts)
                    sp.set(dispatches=1)
                    return [dict(zip(parts[0], r))
                            for r in zip(*(rows[k] for k in parts[0]))]
            return [self.runner(params, x) for x in frames]

    def simulate(self, frames: int = 64):
        """Closed-form steady-state report for the plan (Table 5
        quantities)."""
        from ..core.simulate import simulate
        return simulate(self.pico.pipeline, frames, cluster=self.cluster)

    # ---------------- observability ----------------

    def metrics_snapshot(self, meta: Mapping | None = None) -> dict:
        """Versioned metrics-snapshot document for this deployment.

        Merges the deployment-scoped registry (runtime frame/monitor
        series from runs with ``DeploySpec(metrics=True)``) with the
        process-default registry (executable-cache hits/misses/
        evictions, per-segment compile wall-times, ``conv.fallback``
        counts) into one
        :meth:`~repro.obs.metrics.MetricsRegistry.snapshot` envelope —
        see :func:`repro.obs.metrics.open_snapshot`/``flatten`` for the
        reader side.
        """
        reg = MetricsRegistry()
        reg.merge(self.metrics)
        reg.merge(default_registry())
        base = {"model": getattr(self.model, "name", "model"),
                "devices": len(self.cluster),
                "stages": len(self.pico.pipeline.stages)}
        base.update(meta or {})
        return reg.snapshot(meta=base)

    def save_trace(self, path: str | os.PathLike) -> str:
        """Write the lifecycle trace as Perfetto-loadable Chrome-trace
        JSON (one process row per device); returns the path."""
        return self.tracer.save(path)

    # ---------------- online forms ----------------

    def runtime(self, deploy_spec: DeploySpec | None = None, *,
                churn: Sequence = (), real_compute: bool | None = None):
        """Event-driven cluster runtime over this plan (no re-planning).

        ``real_compute`` defaults to "yes iff params are loaded"; pass
        ``False`` for a timing-only run on a deployment that has
        weights."""
        from ..runtime.executor import PipelineRuntime
        spec = deploy_spec or DeploySpec()
        real = (self.params is not None if real_compute is None
                else real_compute)
        if real and self.params is None:
            self.load_params()
        kw = dict(cluster=self.cluster, pico=self.pico,
                  config=spec.to_runtime_config(), churn=churn,
                  plan_spec=self.plan_spec, exec_spec=self.exec_spec,
                  cost_table=self.cost_table)
        if spec.trace:
            kw["tracer"] = self.tracer       # append to the lifecycle trace
        if spec.metrics:
            kw["metrics"] = self.metrics     # publish into this deployment
        if real:
            return PipelineRuntime(model=self.model, params=self.params,
                                   **kw)
        return PipelineRuntime(g=self.model.graph,
                               input_size=self.model.input_size, **kw)

    def server(self, deploy_spec: DeploySpec | None = None, *,
               streaming: bool = False, churn: Sequence = ()):
        """Serving front-end over this plan: the closed-form
        :class:`~repro.serving.server.PipelineServer`, or (with
        ``streaming=True``) the runtime-backed streaming server."""
        from ..serving.server import PipelineServer, StreamingPipelineServer
        if streaming:
            spec = deploy_spec or DeploySpec()
            srv = StreamingPipelineServer(
                self.model, self.cluster, deploy_spec=spec, churn=churn,
                plan_spec=self.plan_spec, exec_spec=self.exec_spec,
                cost_table=self.cost_table, pico=self.pico)
        else:
            if deploy_spec is not None:
                raise TypeError("deploy_spec applies to the runtime-backed "
                                "server; pass streaming=True (the "
                                "closed-form PipelineServer has no deploy "
                                "knobs)")
            if churn:
                raise TypeError("churn applies to the runtime-backed "
                                "server; pass streaming=True")
            srv = PipelineServer(
                self.model, self.cluster, plan_spec=self.plan_spec,
                exec_spec=self.exec_spec, cost_table=self.cost_table,
                pico=self.pico)
        if self.params is not None:
            srv.params = self.params
        return srv

    def fleet(self, dist_spec=None, **kw):
        """Real distributed execution of this deployment
        (:class:`~repro.dist.launcher.DistLauncher`): one worker per
        pipeline stage — persistent threads or spawned processes per
        :class:`~repro.api.specs.DistSpec` — each rebuilt from this
        deployment's versioned JSON artifact (the artifact round-trip
        is the hand-off).  ``launcher.run(frames)`` executes and
        drains; ``repro.dist.validate(dep)`` pins the outputs
        bit-identical to :meth:`run`.

        Workers re-initialize weights deterministically from
        ``DistSpec.seed`` (the artifact deliberately ships no weights),
        so results match :meth:`run` under the same default params."""
        from ..dist.launcher import DistLauncher
        return DistLauncher(self, dist_spec, **kw)

    def scheduler(self, tenants: Sequence, config=None):
        """Multi-tenant scheduler co-hosting ``tenants``
        (:class:`~repro.serving.scheduler.TenantConfig`) on this
        deployment's cluster, inheriting its exec spec and cost table."""
        from ..serving.scheduler import ServingScheduler
        return ServingScheduler(tenants, self.cluster, config=config,
                                exec_spec=self.exec_spec,
                                cost_table=self.cost_table)

    def replan(self, cluster: Cluster) -> "Deployment":
        """Re-plan onto a changed cluster, reusing Algorithm 1's piece
        chain and any measured cost table (the runtime feedback loop as
        a pure function: old deployment + new cluster -> new one).

        A :class:`~repro.core.pipeline_dp.PlannerCache` is carried
        across the replan chain, so every hop after the first is the
        incremental hot path (``pico.source == "incremental"``)."""
        from ..core.pipeline_dp import PlannerCache
        cache = getattr(self, "_planner_cache", None)
        if cache is None:
            cache = self._planner_cache = PlannerCache()
        pico = plan_with_spec(self.model.graph, cluster,
                              self.model.input_size, self.plan_spec,
                              partition=self.pico.partition,
                              cost_table=self.cost_table,
                              planner_cache=cache)
        dep = Deployment(self.model, cluster, self.plan_spec,
                         self.exec_spec, pico, cost_table=self.cost_table,
                         params=self.params)
        dep._planner_cache = cache
        return dep

    # ---------------- persistence ----------------

    def _payload(self) -> dict:
        return {
            "plan_spec": self.plan_spec.to_dict(),
            "exec_spec": self.exec_spec.to_dict(),
            "model": artifacts.model_to_dict(self.model),
            "cluster": artifacts.cluster_to_dict(self.cluster),
            "pico": artifacts.plan_to_dict(self.pico),
            "cost_table": (None if self.cost_table is None
                           else artifacts.cost_table_to_dict(self.cost_table)),
        }

    def to_dict(self) -> dict:
        return artifacts.envelope("deployment", self._payload())

    @classmethod
    def _from_payload(cls, p: Mapping, model=None, params=None
                      ) -> "Deployment":
        return cls(
            model if model is not None else artifacts.model_from_dict(
                p["model"]),
            artifacts.cluster_from_dict(p["cluster"]),
            PlanSpec.from_dict(p["plan_spec"]),
            ExecSpec.from_dict(p["exec_spec"]),
            artifacts.plan_from_dict(p["pico"]),
            cost_table=(None if p.get("cost_table") is None
                        else artifacts.cost_table_from_dict(p["cost_table"])),
            params=params)

    @classmethod
    def from_dict(cls, d: Mapping, model=None, params=None) -> "Deployment":
        return cls._from_payload(artifacts.open_envelope(d, "deployment"),
                                 model=model, params=params)

    def to_json(self, **dump_kw) -> str:
        return artifacts.dumps_payload("deployment", self._payload(),
                                       **dump_kw)

    @classmethod
    def from_json(cls, s: str, model=None, params=None) -> "Deployment":
        return cls._from_payload(artifacts.loads_payload("deployment", s),
                                 model=model, params=params)

    def save(self, path: str | os.PathLike) -> str:
        """Write the deployment artifact (plan + specs + model def +
        cluster + cost table) as versioned JSON; returns the path.

        Model *weights* are deliberately not part of the artifact —
        the plan ships as data, weights ship as checkpoints.  Default
        weights reproduce exactly on load (``init`` is deterministic in
        the serialized graph + PRNG key); trained weights must be
        reattached via ``Deployment.load(path, params=...)``."""
        with open(path, "w") as f:
            f.write(self.to_json(indent=1))
            f.write("\n")
        return os.fspath(path)

    @classmethod
    def load(cls, path: str | os.PathLike, model=None,
             params=None) -> "Deployment":
        """Rebuild a deployment from :meth:`save` output.  Neither the
        planner nor the calibrator runs — the plan, its measured cost
        table, and the model definition all come from the artifact.
        Pass ``model=`` to attach an existing model object instead of
        rebuilding one from the serialized graph, and ``params=`` to
        reattach trained weights (see :meth:`save`)."""
        with open(path) as f:
            return cls.from_json(f.read(), model=model, params=params)
