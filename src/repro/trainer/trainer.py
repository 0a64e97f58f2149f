"""SpmdTrainer: config-driven distributed training loop (paper §3–§5).

Everything is a replaceable child module: model, learner, input pipeline,
checkpointer. Parallelism is configured — mesh shape + axis names + the
partition specs the layers already carry — never coded (§4.2).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer, CheckpointWriteError
from repro.observability.hardware import compiled_cost, device_memory_stats, estimate_mfu
from repro.observability.runtime import ObservabilityConfig, build_observability
from repro.runtime.goodput import GoodputMonitor
from repro.runtime.signals import Preempted
from repro.core.config import REQUIRED, ConfigBase, Required, config_class
from repro.core.module import Module, no_context
from repro.core.utils import (
    infer_mesh_shape,
    make_mesh,
    named_sharding,
    resolve_spec,
    set_mesh,
    tree_param_count,
)
from repro.data.input import SyntheticInput
from repro.launch.distributed import initialize as distributed_initialize
from repro.layers.base import ParameterSpec
from repro.trainer.learner import Learner
from repro.trainer.optimizers import global_norm
from repro.trainer.train_step import (
    build_train_step,
    canonical_mean,
    combine_microbatch_grads,
    make_loss_fn,
    slice_microbatch,
    zero1_partition_spec,
)

__all__ = ["SpmdTrainer", "TrainState", "WatchdogTimeout"]

TrainState = Dict[str, Any]  # {"step", "prng_key", "params", "opt_state"}


class WatchdogTimeout(RuntimeError):
    """A training step exceeded the configured watchdog timeout (§5)."""


def _flatten_metrics(metrics: Dict[str, Any]) -> Dict[str, float]:
    """Step metrics -> flat {name: float}. Nested dicts (the routed
    ``summaries`` subtree) flatten as ``summaries/<module-path>``, and a
    per-layer summary of a scanned stack as ``summaries/<module-path>/<i>``.
    Forces a host transfer, so call only at the logging cadence."""
    flat: Dict[str, float] = {}
    for k, v in metrics.items():
        if isinstance(v, dict):
            for sk, sv in v.items():
                sv = np.asarray(sv)
                if sv.ndim:
                    for i, x in enumerate(sv):
                        flat[f"{k}/{sk}/{i}"] = float(x)
                else:
                    flat[f"{k}/{sk}"] = float(sv)
        else:
            flat[k] = float(v)
    return flat


def opt_state_shardings(opt_state_shapes: Any, params_structure,
                        param_shardings: Any, mesh, *,
                        param_state_shardings: Any = None) -> Any:
    """Shardings for an optimizer state pytree: any subtree whose structure
    matches the params tree inherits ``param_state_shardings`` (ZeRO-1
    partitioned specs; defaults to the param shardings — moments, master
    weights, SGD velocity are all param-structured); other leaves are
    replicated (counts, schedules)."""
    from jax.sharding import NamedSharding, PartitionSpec

    replicated = NamedSharding(mesh, PartitionSpec()) if mesh else None
    target = param_state_shardings if param_state_shardings is not None \
        else param_shardings

    def rec(node):
        if jax.tree.structure(node) == params_structure:
            return target
        if isinstance(node, tuple) and type(node) is not tuple:  # NamedTuple
            return type(node)(*[rec(x) for x in node])
        if isinstance(node, tuple):
            return tuple(rec(x) for x in node)
        if isinstance(node, list):
            return [rec(x) for x in node]
        if isinstance(node, dict):
            return {k: rec(v) for k, v in node.items()}
        return replicated

    return rec(opt_state_shapes)


class SpmdTrainer(Module):
    @config_class
    class Config(Module.Config):
        model: Required[ConfigBase] = REQUIRED
        learner: Learner.Config = Learner.Config()
        input: SyntheticInput.Config = SyntheticInput.Config()
        checkpointer: Optional[Checkpointer.Config] = None
        # --- parallelism is configuration (paper §4.2) ---
        # One entry may be -1: the devices the other axes leave over.
        mesh_shape: Tuple[int, ...] = (1,)
        mesh_axis_names: Tuple[str, ...] = ("data",)
        batch_partition: Any = (("pod", "data"),)  # applied to dim 0 of inputs
        # FSDP-style parameter sharding: when set, every parameter's first
        # free divisible dim is additionally sharded over these mesh axes
        # (the same partitioning rule ZeRO-1 applies to optimizer state,
        # lifted to the params themselves). Set by FsdpModifier.
        fsdp_axes: Optional[Tuple[str, ...]] = None
        # Elastic multi-process runtime (a repro.launch.distributed
        # .DistributedConfig). When set, run() takes the world-size-
        # invariant step path: the global batch decomposes into
        # ``distributed.grad_microbatches`` canonical microbatches, each
        # process computes its block, contributions are allgathered and
        # combined in canonical order on the host — bitwise-identical
        # updates at every world size (reshard-on-resume continuity).
        # Set by ElasticModifier.
        distributed: Optional[ConfigBase] = None
        # --- loop ---
        max_steps: int = 100
        seed: int = 0
        log_every_n: int = 10
        checkpoint_every_n: int = 0
        # Gradient accumulation (microbatching) — memory lever.
        grad_accum_steps: int = 1
        # Dtype gradients are ACCUMULATED in across microbatches (None ->
        # each param's dtype). Set by DtypePolicyModifier from the policy's
        # grad_dtype.
        grad_dtype: Any = None
        # Optimizer-state sharding: "params" replicates the opt state like
        # the params; "zero1" additionally partitions every param-shaped
        # optimizer leaf (moments, master weights) along the data axes —
        # per-device optimizer bytes shrink ~Nx on an N-way data mesh.
        opt_state_sharding: str = "params"
        zero1_axes: Tuple[str, ...] = ("pod", "data")
        # Optimizer-state host offload (TPU feature).
        offload_optimizer_state: bool = False
        # Unified observability (repro.observability): metrics registry +
        # JSONL sink, Chrome trace spans per step phase, HFU/memory gauges,
        # on-demand profiler window. None = zero instrumentation.
        observability: Optional[ObservabilityConfig] = None
        # Runtime resiliency (paper §5).
        watchdog_timeout_s: Optional[float] = None
        # "warn" prints; "raise" raises WatchdogTimeout at the next
        # heartbeat after a step overran (the async dispatch returns to the
        # host every step, so a hung device shows up at the next beat).
        watchdog_on_timeout: str = "warn"
        sdc_check_every_n: int = 0

    def __init__(self, cfg, *, parent=None):
        super().__init__(cfg, parent=parent)
        self._add_child("model", cfg.model)
        self._add_child("learner", cfg.learner)
        self._add_child("input", cfg.input)
        if cfg.checkpointer is not None:
            self._add_child("checkpointer", cfg.checkpointer)
        self._mesh = None
        self._jit_step = None
        self._step_has_run = False
        # Telemetry bundle (engine-cached like the jitted step: one registry
        # / tracer / profiler across warm restarts on this instance).
        self.observability = build_observability(cfg.observability)
        self._step_cost = None
        self._opt_state_bytes = None
        self._mem_stats_unavailable = False
        self._lower_shapes = None
        # Set by a SIGTERM handler (see launch/train.py) or the supervisor's
        # fault injection; the loop polls it at each step boundary, takes a
        # synchronous emergency checkpoint, and raises Preempted.
        self.preemption_event = threading.Event()

    # ----------------------------------------------------------------- setup

    @no_context
    def build_mesh(self):
        cfg = self.config
        if self._mesh is None:
            shape = infer_mesh_shape(cfg.mesh_shape, len(jax.devices()))
            n = int(np.prod(shape))
            if n > len(jax.devices()):
                raise RuntimeError(
                    f"mesh {cfg.mesh_shape} needs {n} devices, "
                    f"have {len(jax.devices())}")
            self._mesh = make_mesh(shape, cfg.mesh_axis_names,
                                   devices=jax.devices()[:n])
        return self._mesh

    @no_context
    def param_specs(self):
        return self.model.create_parameter_specs_recursively()

    @no_context
    def param_shardings(self, mesh=None):
        mesh = mesh or self.build_mesh()
        cfg = self.config
        specs = self.param_specs()
        if cfg.fsdp_axes:
            from jax.sharding import NamedSharding

            # FSDP: params shard over the data axes with the same first-
            # free-divisible-dim rule ZeRO-1 uses for optimizer state (a
            # param that already uses an axis, or has no dividing dim, keeps
            # its own spec).
            return jax.tree.map(
                lambda s: NamedSharding(
                    mesh, zero1_partition_spec(s, mesh, cfg.fsdp_axes)),
                specs, is_leaf=lambda s: isinstance(s, ParameterSpec))
        return jax.tree.map(
            lambda s: named_sharding(s.mesh_axes, mesh), specs,
            is_leaf=lambda s: isinstance(s, ParameterSpec))

    @no_context
    def batch_shardings(self, batch_like, mesh=None):
        mesh = mesh or self.build_mesh()
        cfg = self.config

        def shard(x):
            ndim = len(x.shape)
            spec = tuple(cfg.batch_partition) + (None,) * (ndim - len(cfg.batch_partition))
            return named_sharding(spec[:ndim], mesh)

        return jax.tree.map(shard, batch_like)

    # ------------------------------------------------------------------ state

    @no_context
    def init_state(self, prng_key: Optional[jax.Array] = None) -> TrainState:
        cfg = self.config
        if prng_key is None:
            prng_key = jax.random.PRNGKey(cfg.seed)
        self.learner.build(self.param_specs())
        params = self.model.initialize_parameters_recursively(prng_key)
        opt_state = self.learner.init_state(params)
        return {
            "step": jnp.zeros((), jnp.int32),
            "prng_key": prng_key,
            "params": params,
            "opt_state": opt_state,
        }

    @no_context
    def zero1_partition_specs(self, mesh=None):
        """Tree (matching params) of ZeRO-1 PartitionSpecs for param-shaped
        optimizer-state leaves."""
        mesh = mesh or self.build_mesh()
        cfg = self.config
        return jax.tree.map(
            lambda s: zero1_partition_spec(s, mesh, cfg.zero1_axes),
            self.param_specs(),
            is_leaf=lambda s: isinstance(s, ParameterSpec))

    @no_context
    def state_shardings(self, state_shapes: TrainState, mesh=None):
        from jax.sharding import NamedSharding, PartitionSpec

        mesh = mesh or self.build_mesh()
        cfg = self.config
        p_shardings = self.param_shardings(mesh)
        opt_leaf_sh = None
        if cfg.opt_state_sharding == "zero1":
            opt_leaf_sh = jax.tree.map(
                lambda spec: NamedSharding(mesh, spec),
                self.zero1_partition_specs(mesh))
        elif cfg.opt_state_sharding != "params":
            raise ValueError(
                f"Unknown opt_state_sharding {cfg.opt_state_sharding!r}; "
                "expected 'params' or 'zero1'")
        opt_sh = opt_state_shardings(
            state_shapes["opt_state"], jax.tree.structure(state_shapes["params"]),
            p_shardings, mesh, param_state_shardings=opt_leaf_sh)
        if cfg.offload_optimizer_state:
            opt_sh = jax.tree.map(
                lambda s: s.with_memory_kind("pinned_host") if s is not None else s,
                opt_sh)
        rep = NamedSharding(mesh, PartitionSpec())
        return {
            "step": rep,
            "prng_key": rep,
            "params": p_shardings,
            "opt_state": opt_sh,
        }

    # ------------------------------------------------------------- train step

    @no_context
    def make_train_step(self) -> Callable[[TrainState, Dict[str, Any]],
                                          Tuple[TrainState, Dict[str, Any]]]:
        """Builds the jittable step from the composable pieces in
        ``repro.trainer.train_step`` (loss -> accumulated grads -> sharded
        optimizer update)."""
        cfg = self.config
        update_specs = param_specs = None
        if cfg.opt_state_sharding == "zero1":
            mesh = self.build_mesh()
            update_specs = self.zero1_partition_specs(mesh)
            param_specs = jax.tree.map(
                lambda s: resolve_spec(s.mesh_axes, mesh), self.param_specs(),
                is_leaf=lambda s: isinstance(s, ParameterSpec))
        return build_train_step(
            self.model,
            self.learner,
            aux_loss_weight=cfg.learner.aux_loss_weight,
            aux_loss_pattern=cfg.learner.aux_loss_pattern,
            grad_accum_steps=cfg.grad_accum_steps,
            grad_dtype=cfg.grad_dtype,
            update_partition_specs=update_specs,
            param_partition_specs=param_specs,
        )

    # ----------------------------------------------------------- elastic step

    @no_context
    def _make_elastic_step(self, shardings) -> Callable:
        """The world-size-invariant step for elastic multi-process training.

        The global batch (every process holds the identical global batch —
        the ElasticModifier configures the input with the global view)
        decomposes into G = ``distributed.grad_microbatches`` canonical
        microbatches. Process p computes microbatches
        ``[p*G/N, (p+1)*G/N)`` with ONE jitted per-microbatch program whose
        shapes do not depend on the world size, allgathers the float32
        contributions, and every process folds all G of them in canonical
        order with left-associative host arithmetic before one jitted
        optimizer-update program. Same programs + same data + same
        reduction order ⇒ bitwise-identical states at every world size —
        a checkpoint committed at world size P resumes at P' with the loss
        curve of the uninterrupted run.
        """
        cfg = self.config
        dcfg = cfg.distributed
        N = dcfg.process_count
        G = dcfg.grad_microbatches or N
        if G % max(N, 1) != 0:
            raise ValueError(
                f"grad_microbatches={G} must be divisible by process_count="
                f"{N} (set it to the LCM of every world size the job may "
                "run at)")
        if getattr(cfg.input, "process_count", 1) != 1:
            raise ValueError(
                "elastic training requires the global-view input contract "
                "(input.process_count == 1 on every rank; the trainer "
                "slices canonical microbatches itself) — apply "
                "ElasticModifier instead of sharding the input")
        collective = distributed_initialize(dcfg)  # None at world size 1
        per_rank = G // max(N, 1)
        mine = range(dcfg.process_index * per_rank,
                     (dcfg.process_index + 1) * per_rank)

        loss_fn = make_loss_fn(
            self.model, aux_loss_weight=cfg.learner.aux_loss_weight,
            aux_loss_pattern=cfg.learner.aux_loss_pattern)
        mb_grad_fn = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))

        learner = self.learner
        update_specs = param_specs = None
        if cfg.opt_state_sharding == "zero1":
            mesh = self.build_mesh()
            update_specs = self.zero1_partition_specs(mesh)
            param_specs = jax.tree.map(
                lambda s: resolve_spec(s.mesh_axes, mesh), self.param_specs(),
                is_leaf=lambda s: isinstance(s, ParameterSpec))

        def apply_updates(state, grads):
            new_params, new_opt = learner.apply_updates(
                grads, state["opt_state"], state["params"],
                update_partition_specs=update_specs,
                param_partition_specs=param_specs)
            new_state = {
                "step": state["step"] + 1,
                "prng_key": state["prng_key"],
                "params": new_params,
                "opt_state": new_opt,
            }
            return new_state, global_norm(grads)

        apply_fn = jax.jit(apply_updates, donate_argnums=(0,))

        def elastic_step(state, batch):
            step_key = jax.random.fold_in(state["prng_key"], state["step"])
            payload: Dict[str, np.ndarray] = {}
            treedef = None
            n_leaves = 0
            for m in mine:
                mb = slice_microbatch(batch, m, G)
                mb_key = jax.random.fold_in(step_key, m)
                (total, parts), grads = mb_grad_fn(state["params"], mb,
                                                   mb_key)
                leaves, treedef = jax.tree_util.tree_flatten(grads)
                n_leaves = len(leaves)
                for i, leaf in enumerate(leaves):
                    # float32 exchange: bitwise-stable through the .npz
                    # roundtrip and under numpy accumulation on every rank.
                    payload[f"{m:05d}.g{i:05d}"] = np.asarray(
                        leaf, np.float32)
                payload[f"{m:05d}.metrics"] = np.asarray(
                    [total, parts["loss"], parts["aux_loss"]], np.float32)
            if collective is None:
                merged = payload
            else:
                merged = {}
                for contribution in collective.allgather(payload):
                    merged.update(contribution)
            per_mb = [[merged[f"{m:05d}.g{i:05d}"] for i in range(n_leaves)]
                      for m in range(G)]
            grads = combine_microbatch_grads(per_mb, treedef)
            scalar_means = canonical_mean(
                [merged[f"{m:05d}.metrics"] for m in range(G)])
            new_state, grad_norm = apply_fn(state, grads)
            metrics = {
                "total_loss": scalar_means[0],
                "grad_norm": grad_norm,
                "loss": scalar_means[1],
                "aux_loss": scalar_means[2],
            }
            return new_state, metrics

        return elastic_step

    # ---------------------------------------------------------- hardware cost

    @no_context
    def step_cost_analysis(self) -> Dict[str, Any]:
        """XLA's own analysis of the compiled train step: ``flops`` (the
        HFU numerator), ``bytes_accessed``, and ``peak_hbm_proxy_bytes``
        (argument + temp + output bytes of the executable).

        Memoized per trainer; the one extra lower+compile happens off the
        step path (first logging step, or on demand from the bench). A
        failing compile or analysis raises. Returns ``{}`` before the step
        is built and for the elastic multi-process step (not a single
        jitted program).
        """
        if self._step_cost is not None:
            return self._step_cost
        if (self.config.distributed is not None or self._jit_step is None
                or self._lower_shapes is None):
            return {}
        state_shapes, batch_abs = self._lower_shapes
        compiled = self._jit_step.lower(state_shapes, batch_abs).compile()
        cost = compiled_cost(compiled)
        ma = compiled.memory_analysis()
        cost["peak_hbm_proxy_bytes"] = int(
            ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes)
        self._step_cost = cost
        return cost

    # -------------------------------------------------------------------- run

    @no_context
    def run(self, num_steps: Optional[int] = None, *,
            monitor: Optional[GoodputMonitor] = None,
            step_hook: Optional[Callable[..., None]] = None) -> Dict[str, Any]:
        """Runs the training loop inside the fault-tolerance runtime.

        ``monitor`` attributes wall time to goodput buckets (a fresh one is
        created if not given; the supervisor passes one spanning restarts).
        ``step_hook(step=, state=, metrics=, trainer=)`` fires after every
        step (fault injection, custom telemetry).

        Checkpoints carry the input iterator's state, so a resume replays
        no data and skips none (exactly-once). When ``preemption_event`` is
        set, the loop takes a synchronous emergency checkpoint at the next
        step boundary and raises :class:`Preempted`.
        """
        import contextlib

        cfg = self.config
        num_steps = num_steps or cfg.max_steps
        obs = self.observability
        registry = obs.registry if obs is not None else None
        tracer = obs.tracer if obs is not None else None
        monitor = monitor if monitor is not None else GoodputMonitor()
        if registry is not None and monitor._sink is None:
            # The goodput monitor's event stream adopts the unified schema:
            # every bucket exit lands in the registry's sinks as
            # {"kind": "event", "name": "goodput/<bucket>", ...}.
            monitor._sink = registry.goodput_sink()

        @contextlib.contextmanager
        def phase(name, **meta):
            """One run phase: a goodput bucket, and (when tracing) a span
            on this rank's timeline lane. Host-side only — zero retraces."""
            if tracer is None:
                with monitor.bucket(name, **meta):
                    yield
            else:
                with monitor.bucket(name, **meta), tracer.span(name, **meta):
                    yield

        mesh = self.build_mesh()
        with set_mesh(mesh):
            with phase("init"):
                state = self.init_state()
                state_shapes = jax.eval_shape(lambda: state)
                shardings = self.state_shardings(state_shapes, mesh)
                state = jax.device_put(state, shardings)

                # Exact optimizer-state footprint (repro.memopt.accounting),
                # computed on shapes (no device transfer): the lever the
                # memory-frugal knobs (factored/quantized state, ZeRO-1)
                # move, exported as gauges and in the run result.
                from repro.memopt import accounting

                self._opt_state_bytes = accounting.state_bytes(
                    state_shapes["opt_state"])
                opt_bytes_per_device = accounting.per_device_state_bytes(
                    state_shapes["opt_state"], shardings["opt_state"])
                if registry is not None:
                    registry.gauge("train/opt_state_bytes").set(
                        self._opt_state_bytes)
                    if opt_bytes_per_device is not None:
                        registry.gauge(
                            "train/opt_state_bytes_per_device").set(
                                opt_bytes_per_device)

                sample = self.input.make_batch(0)
                batch_sh = self.batch_shardings(sample, mesh)
                self._lower_shapes = (state_shapes, {
                    k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                    for k, v in sample.items()})
                tokens_per_step = int(getattr(
                    sample.get("input_ids", sample.get("labels", None)),
                    "size", 0))
            # The jitted step is engine-cached: repeated run() calls on one
            # trainer (warm restarts, resume-after-checkpoint) reuse the
            # compiled executable — the train step compiles exactly once.
            if self._jit_step is None:
                if cfg.distributed is not None:
                    self._jit_step = self._make_elastic_step(shardings)
                else:
                    self._jit_step = jax.jit(
                        self.make_train_step(),
                        in_shardings=(shardings, batch_sh),
                        out_shardings=(shardings, None),
                        donate_argnums=(0,),
                    )
            step_fn = self._jit_step

            it = self.input.batches()
            start_step = 0
            if cfg.checkpointer is not None:
                latest = self.checkpointer.latest_step()
                if latest is not None:
                    with phase("restore", step=latest):
                        state = self.checkpointer.restore(latest, like=state)
                        state = jax.device_put(state, shardings)
                        # Elastic mode uses the global-view input contract:
                        # every rank's iterator state is identical, so a
                        # checkpoint committed at world size P restores into
                        # P' ranks by reading rank 0's aux — the reshard is
                        # a no-op by construction.
                        aux = self.checkpointer.restore_aux(
                            latest,
                            process_index=0 if cfg.distributed is not None
                            else None)
                        if aux and "input" in aux and hasattr(it, "restore"):
                            it.restore(aux["input"])
                        elif hasattr(it, "restore"):
                            print(f"[trainer] checkpoint step {latest} has no "
                                  "input-iterator state; data stream restarts "
                                  "from the beginning (pre-aux checkpoint?)")
                    start_step = latest

            watchdog = _Watchdog(cfg.watchdog_timeout_s,
                                 on_timeout=cfg.watchdog_on_timeout)
            history = []
            t0 = time.time()
            fetched = None  # (perf_counter, step) of the last logging fetch
            last_metrics = {}
            try:
                for step in range(start_step, num_steps):
                    if self.preemption_event.is_set():
                        committed = False
                        if cfg.checkpointer is not None:
                            with phase("checkpoint_stall", step=step,
                                       emergency=True):
                                try:
                                    committed = self.checkpointer.emergency_save(
                                        step, state, aux={"input": it.state()}
                                        if hasattr(it, "state") else None) is not None
                                except CheckpointWriteError as e:
                                    # Stay on the Preempted protocol (exit
                                    # 143, resumable from an OLDER step)
                                    # even if the emergency commit failed —
                                    # e.g. a peer process died before its
                                    # shard and the short barrier timed out.
                                    print(f"[trainer] emergency save failed: {e}")
                        raise Preempted(step, committed=committed)
                    with phase("input_stall", step=step):
                        batch = next(it)
                    batch = jax.device_put(batch, batch_sh)
                    watchdog.beat(step)
                    if obs is not None:
                        obs.profiler.on_step_start(step)
                    # The first invocation traces + XLA-compiles; attribute
                    # it to "compile" (it includes that one step's compute).
                    warm = self._step_has_run
                    with phase("compile" if not warm else "step", step=step):
                        state, metrics = step_fn(state, batch)
                        if (not warm and obs is not None and obs.config.mfu
                                and not cfg.distributed):
                            # Pre-pay the HFU numerator's one extra AOT
                            # lower+compile here, in the compile bucket —
                            # never in a warm step (which must stay within
                            # the <1% instrumentation budget).
                            self.step_cost_analysis()
                    self._step_has_run = True
                    if obs is not None:
                        obs.profiler.on_step_end(step)
                    if cfg.sdc_check_every_n and step % cfg.sdc_check_every_n == 0:
                        self._sdc_check(batch)
                    if step % cfg.log_every_n == 0 or step == num_steps - 1:
                        m = _flatten_metrics(metrics)  # blocks on the step
                        # Dispatch is asynchronous, so the step time is the
                        # wall clock between two blocking fetches over the
                        # steps between them; the compile comes before the
                        # first fetch and is in no interval.
                        now = time.perf_counter()
                        step_dur = ((now - fetched[0]) / (step - fetched[1])
                                    if fetched is not None else None)
                        fetched = (now, step)
                        m["step"] = step
                        m["steps_per_s"] = (step - start_step + 1) / (time.time() - t0)
                        history.append(m)
                        last_metrics = m
                        if registry is not None:
                            self._export_step_metrics(
                                registry, m, step, step_dur=step_dur,
                                tokens_per_step=tokens_per_step)
                    if (cfg.checkpointer is not None and cfg.checkpoint_every_n
                            and (step + 1) % cfg.checkpoint_every_n == 0):
                        # Async save: the training thread pays only the
                        # device-side snapshot (+ any still-in-flight save);
                        # staging and the write run in the background.
                        with phase("checkpoint_stall", step=step):
                            self.checkpointer.save(
                                step + 1, state, aux={"input": it.state()}
                                if hasattr(it, "state") else None)
                    if step_hook is not None:
                        step_hook(step=step, state=state, metrics=metrics,
                                  trainer=self)
            except KeyboardInterrupt:
                # The watchdog timer interrupts the main thread on timeout
                # in "raise" mode; convert to the typed error. A genuine
                # Ctrl-C (watchdog never fired) re-raises unchanged.
                watchdog.check()
                raise
            finally:
                if hasattr(it, "close"):
                    it.close()
                # Disarm the timer on EVERY exit (a Preempted/fault-injected
                # unwind must not leave a live timer to interrupt the next
                # supervisor attempt). cancel() does not check(): a pending
                # WatchdogTimeout must not mask the in-flight exception.
                watchdog.cancel()
                # Telemetry survives every exit path: a preempted/crashed
                # run still leaves its trace + flushed metrics behind.
                if obs is not None:
                    obs.profiler.close()
                    registry.drain()
                    obs.save_trace()
            watchdog.stop()
            if cfg.checkpointer is not None:
                with phase("checkpoint_stall", step=num_steps,
                           final_wait=True):
                    self.checkpointer.wait()
            if obs is not None:
                obs.save_trace()  # include the final-wait span
            return {"state": state, "history": history, "final": last_metrics,
                    "num_params": tree_param_count(state["params"]),
                    "opt_state_bytes": self._opt_state_bytes,
                    "input_state": it.state() if hasattr(it, "state") else None,
                    "goodput": monitor.summary(),
                    "goodput_events": monitor.events,
                    "telemetry": obs.snapshot() if obs is not None else None,
                    "step_cost": dict(self._step_cost or {})}

    def _export_step_metrics(self, registry, m: Dict[str, float], step: int,
                             *, step_dur: Optional[float] = None,
                             tokens_per_step: int = 0):
        """Routes one logging step's metrics into the registry: gauges keyed
        ``train/<metric>`` and ``summaries/<module-path>`` (the values
        modules ``add_summary``'d, routed out of the jitted step), plus
        hardware gauges — ``hardware/hfu`` (hardware FLOPs utilization: the
        compiled step's own FLOPs, remat recompute included, over peak),
        tokens/s/device over ``step_dur`` (seconds per step between two
        blocking fetches; None when there is no earlier fetch), and
        ``device.memory_stats()`` where the backend reports them (TPU/GPU
        peak HBM; empty on CPU)."""
        obs = self.observability
        for k, v in m.items():
            if k == "step":
                continue
            name = k if k.startswith("summaries/") else f"train/{k}"
            registry.gauge(name).set(v)
        if step_dur and step_dur > 0:
            registry.histogram("train/step_time_s").record(step_dur)
            n_dev = self.build_mesh().size
            if tokens_per_step:
                registry.gauge("train/tokens_per_s").set(
                    tokens_per_step / step_dur)
                registry.gauge("train/tokens_per_s_per_device").set(
                    tokens_per_step / step_dur / n_dev)
            if obs.config.mfu:
                cost = self.step_cost_analysis()
                hfu = estimate_mfu(
                    cost.get("flops"), step_dur, num_devices=n_dev,
                    peak_flops_per_device=obs.config.peak_flops_per_device)
                if hfu is not None:
                    registry.gauge("hardware/hfu").set(hfu)
                if cost.get("flops"):
                    registry.gauge("hardware/step_flops").set(cost["flops"])
                if cost.get("peak_hbm_proxy_bytes"):
                    registry.gauge("hardware/peak_hbm_proxy_bytes").set(
                        cost["peak_hbm_proxy_bytes"])
        if not self._mem_stats_unavailable:
            stats = device_memory_stats()
            # Backends without memory stats (CPU) answer {} every time —
            # probe once, don't pay the query on every logging step.
            self._mem_stats_unavailable = not stats
            for k, v in stats.items():
                registry.gauge(f"hardware/memory/{k}").set(v)
        registry.flush()

    def _sdc_check(self, batch):
        """Paper §5: repeat a computation and compare for silent corruption."""
        x = batch[sorted(batch.keys())[0]]
        f = jax.jit(lambda a: jnp.sum(a.astype(jnp.float32) * 1.000001))
        r1, r2 = f(x), f(x)
        if not np.allclose(np.asarray(r1), np.asarray(r2)):
            raise RuntimeError(f"SDC detected: {r1} != {r2}")


class _Watchdog:
    """Warns (or raises) when a training step exceeds the timeout (§5).

    ``on_timeout="warn"`` prints and keeps going; ``on_timeout="raise"``
    raises :class:`WatchdogTimeout` from the training thread: the timer
    thread interrupts the main thread (``_thread.interrupt_main()`` — the
    run loop converts the resulting KeyboardInterrupt), and as a fallback
    for interrupt-immune blocking calls the next ``beat()``/``stop()``
    raises directly.
    """

    def __init__(self, timeout_s: Optional[float], on_timeout: str = "warn"):
        import threading

        if on_timeout not in ("warn", "raise"):
            raise ValueError(
                f"watchdog on_timeout must be 'warn' or 'raise', got "
                f"{on_timeout!r}")
        self.timeout = timeout_s
        self.on_timeout = on_timeout
        self._timer: Optional[threading.Timer] = None
        self.fired = []

    def _fire(self, step: int):
        self.fired.append(step)
        print(f"[watchdog] step {step} exceeded {self.timeout}s")
        if self.on_timeout == "raise":
            import _thread

            # Raises KeyboardInterrupt in the main thread (at the next
            # bytecode boundary) so a hung host loop actually unblocks;
            # SpmdTrainer.run converts it to WatchdogTimeout via check().
            _thread.interrupt_main()

    def check(self):
        if self.fired and self.on_timeout == "raise":
            raise WatchdogTimeout(
                f"Training step(s) {self.fired} exceeded the watchdog "
                f"timeout of {self.timeout}s")

    def beat(self, step: int):
        import threading

        if self.timeout is None:
            return
        self.stop()
        self._timer = threading.Timer(self.timeout, self._fire, args=(step,))
        self._timer.daemon = True
        self._timer.start()

    def cancel(self):
        """Disarms the timer without raising (safe inside ``finally``)."""
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None

    def stop(self):
        self.cancel()
        self.check()
