"""Distributed launch layer: multi-process init, collectives, worker entry.

The bridge from "fault-tolerant process" to "fault-tolerant cluster"
(paper §5): N OS processes train the same job, synchronize gradients every
step, commit checkpoints through the checkpointer's cross-process barrier,
and can be killed/restarted — at a *different* world size — by the
:class:`~repro.runtime.supervisor.FleetSupervisor`.

Two coordination backends:

* ``"jax"`` — real clusters: :func:`initialize` calls
  ``jax.distributed.initialize(coordinator_address, num_processes,
  process_id)`` and collectives ride the jax runtime
  (``multihost_utils.process_allgather``). Reduction order across hosts is
  then backend-defined, so bitwise world-size invariance is NOT guaranteed;
  use a tolerance when comparing loss curves.
* ``"file"`` — the local test substrate: subprocess workers on one host
  rendezvous through a shared *coordination directory*
  (:class:`FileCollective`). Payload files are written atomically
  (tmp+rename, the same discipline as checkpoint shards), every collective
  is numbered, and a peer that dies surfaces as a
  :class:`DistributedTimeout` instead of a silent hang — the worker then
  exits non-zero and the fleet supervisor restarts the job.

The elastic numerics contract (why a P-process run can resume at P'≠P with
an *identical* loss curve): the global batch is decomposed into a FIXED
number of canonical microbatches ``grad_microbatches`` (independent of
world size; every admissible world size must divide it). Each process
computes per-microbatch gradients for its contiguous block with one shared
jitted program, all contributions are allgathered, and every process sums
them in canonical microbatch order 0..G-1 on the host. Same programs, same
data, same addition order ⇒ bitwise-identical updates at every world size.

Worker mode (what the fleet supervisor spawns)::

    python -m repro.launch.distributed \
        --builder repro.launch.distributed:build_tiny_fleet_config \
        --builder-kwargs '{"steps": 12}' \
        --coordinator-dir /tmp/coord --process-index 0 --process-count 2 \
        --grad-microbatches 2 --checkpoint-dir /tmp/ckpt --result r0.jsonl

Fault-injection flags (used by the supervisor's drills):
``--sigkill-at-step S`` raises SIGKILL against itself in the step hook of
step S (exact step boundary; if S just launched an async save, the write is
in flight — the mid-save kill); ``--sigterm-at-step S`` sets the preemption
event at step S (the SIGTERM drill, deterministic at a boundary);
``--kill-during-save-step S`` dies INSIDE ``_write_step`` of the save for
step S after leaving a torn tmp shard behind — the torn-commit scenario.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

from repro.core.config import ConfigBase, config_class

__all__ = [
    "DistributedConfig",
    "DistributedTimeout",
    "FileCollective",
    "initialize",
    "worker_argv",
    "build_tiny_fleet_config",
]


class DistributedTimeout(RuntimeError):
    """A collective timed out waiting for peer processes (dead rank?)."""


@config_class
class DistributedConfig(ConfigBase):
    """Elastic multi-process runtime configuration (trainer sub-config).

    ``grad_microbatches`` is the canonical gradient decomposition G: the
    global batch is always split into G fixed microbatches regardless of
    world size (0 ⇒ G = process_count, which is NOT world-size invariant —
    set G explicitly to the LCM of every world size the job may run at if
    you need exact loss-curve continuity across resharding).
    """

    coordinator_dir: str = ""
    process_index: int = 0
    process_count: int = 1
    grad_microbatches: int = 0
    collective_timeout_s: float = 60.0
    backend: str = "file"  # "file" | "jax"
    coordinator_address: str = ""  # host:port, jax backend only


class FileCollective:
    """Filesystem rendezvous for same-host multi-process training.

    Every collective is a numbered *op*; all processes must issue the same
    ops in the same order (SPMD discipline). Rank ``p`` publishes its
    payload as ``op<k>_r<p>.npz`` via atomic tmp+rename (existence implies
    completeness), then waits for all ``process_count`` files. A rank
    starting op ``k`` has proven every rank finished reading op ``k-2``, so
    it deletes its own ``k-2`` file — the directory stays O(2N) files.
    """

    def __init__(self, directory: str, *, process_index: int,
                 process_count: int, timeout_s: float = 60.0):
        self.directory = directory
        self.process_index = process_index
        self.process_count = process_count
        self.timeout_s = timeout_s
        self._op = 0
        os.makedirs(directory, exist_ok=True)

    def _path(self, op: int, rank: int) -> str:
        return os.path.join(self.directory, f"op{op:08d}_r{rank}.npz")

    def allgather(self, payload: Dict[str, np.ndarray]
                  ) -> List[Dict[str, np.ndarray]]:
        """Gathers one flat ``{key: array}`` dict per rank, returned in rank
        order. Keys may differ across ranks (each contributes its own
        microbatches); values round-trip bitwise through ``.npz``."""
        op, self._op = self._op, self._op + 1
        stale = self._path(op - 2, self.process_index)
        if op >= 2 and os.path.exists(stale):
            os.remove(stale)
        mine = self._path(op, self.process_index)
        np.savez(mine + ".tmp.npz",
                 **{k: np.asarray(v) for k, v in payload.items()})
        os.replace(mine + ".tmp.npz", mine)
        deadline = time.monotonic() + self.timeout_s
        wanted = [self._path(op, r) for r in range(self.process_count)]
        while not all(os.path.exists(p) for p in wanted):
            if time.monotonic() > deadline:
                missing = [r for r, p in enumerate(wanted)
                           if not os.path.exists(p)]
                raise DistributedTimeout(
                    f"collective op {op} timed out after {self.timeout_s}s "
                    f"waiting for rank(s) {missing} (dead peer?)")
            time.sleep(0.002)
        out = []
        for p in wanted:
            with np.load(p) as z:
                out.append({k: z[k] for k in z.files})
        return out

    def barrier(self):
        self.allgather({})


class _JaxCollective:
    """Collectives over an initialized ``jax.distributed`` runtime (real
    clusters). Gather order is by process index; cross-host numerics are
    backend-defined (see module docstring)."""

    def __init__(self, process_index: int, process_count: int):
        self.process_index = process_index
        self.process_count = process_count

    def allgather(self, payload):
        from jax.experimental import multihost_utils

        # Each rank's keys differ; exchange via a jsonable key manifest +
        # stacked arrays would be heavy — gather the whole dict pickled.
        import pickle

        blob = np.frombuffer(pickle.dumps(payload), np.uint8)
        padded = np.zeros(int(np.max(multihost_utils.process_allgather(
            np.asarray([blob.size])))), np.uint8)
        padded[:blob.size] = blob
        sizes = multihost_utils.process_allgather(np.asarray([blob.size]))
        blobs = multihost_utils.process_allgather(padded)
        return [pickle.loads(blobs[r][:int(sizes[r][0])].tobytes())
                for r in range(self.process_count)]

    def barrier(self):
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices("repro-barrier")


def initialize(cfg) -> Optional[object]:
    """Returns the collective for ``cfg`` (a :class:`DistributedConfig`).

    ``backend="jax"`` initializes the jax distributed runtime (idempotent
    across calls within a process); ``backend="file"`` needs only the
    coordination directory. World size 1 returns None — the elastic step
    path then skips the exchange entirely (lossless: npz round-trips are
    bitwise, so skipping I/O changes nothing).
    """
    if cfg.process_count <= 1:
        return None
    if cfg.backend == "jax":
        import jax

        if not jax.distributed.is_initialized():
            jax.distributed.initialize(
                coordinator_address=cfg.coordinator_address or None,
                num_processes=cfg.process_count,
                process_id=cfg.process_index)
        return _JaxCollective(cfg.process_index, cfg.process_count)
    if cfg.backend == "file":
        if not cfg.coordinator_dir:
            raise ValueError("file backend needs coordinator_dir")
        return FileCollective(cfg.coordinator_dir,
                              process_index=cfg.process_index,
                              process_count=cfg.process_count,
                              timeout_s=cfg.collective_timeout_s)
    raise ValueError(f"Unknown distributed backend {cfg.backend!r}")


# ---------------------------------------------------------------------------
# Worker entry (what the fleet supervisor / local launcher spawns)
# ---------------------------------------------------------------------------


def _resolve_builder(spec: str):
    """'module.path:function' -> the callable."""
    mod_name, _, fn_name = spec.partition(":")
    if not fn_name:
        raise ValueError(f"builder must be 'module:function', got {spec!r}")
    return getattr(importlib.import_module(mod_name), fn_name)


def build_tiny_fleet_config(*, steps: int = 12, checkpoint_every_n: int = 4,
                            vocab: int = 32, dim: int = 32, batch: int = 8,
                            seq: int = 16, seed: int = 1, lr: float = 1e-2,
                            streaming: bool = False):
    """The default worker config: the same tiny CausalLM the runtime tests
    train, with a resumable input. Fleet-agnostic — the worker applies
    :class:`~repro.trainer.mesh_rules.ElasticModifier` on top."""
    from repro.core.config import config_for_function
    from repro.layers import CausalLM, Decoder, Repeat, TransformerLayer
    from repro.trainer import optimizers as opt_lib
    from repro.trainer.trainer import SpmdTrainer

    layer = TransformerLayer.default_config().set(input_dim=dim)
    layer.self_attention.set(num_heads=4, num_kv_heads=2)
    layer.feed_forward.set(hidden_dim=2 * dim)
    model = CausalLM.default_config().set(
        decoder=Decoder.default_config().set(
            vocab_size=vocab, dim=dim,
            stack=Repeat.default_config().set(layer=layer, num_layers=2,
                                              remat_policy=None)))
    cfg = SpmdTrainer.default_config().set(
        name="fleet_worker", model=model, max_steps=steps, log_every_n=1,
        seed=seed, checkpoint_every_n=checkpoint_every_n)
    if streaming:
        from repro.data.streaming import StreamingTextInput

        cfg.input = StreamingTextInput.default_config().set(
            vocab_size=vocab, seq_len=seq, global_batch_size=batch,
            prefetch=0)
    else:
        cfg.input.set(task="lm", vocab_size=vocab, seq_len=seq,
                      global_batch_size=batch)
    cfg.learner.optimizer = config_for_function(opt_lib.adamw).set(
        peak_lr=lr)
    return cfg


def worker_argv(python: str, *, builder: str, builder_kwargs: dict,
                coordinator_dir: str, process_index: int, process_count: int,
                grad_microbatches: int, checkpoint_dir: str, result: str,
                steps: Optional[int] = None,
                collective_timeout_s: float = 60.0,
                trace: str = "",
                sigkill_at_step: Optional[int] = None,
                sigterm_at_step: Optional[int] = None,
                kill_during_save_step: Optional[int] = None) -> List[str]:
    """The exact argv the fleet supervisor spawns for one rank."""
    argv = [python, "-m", "repro.launch.distributed",
            "--builder", builder,
            "--builder-kwargs", json.dumps(builder_kwargs),
            "--coordinator-dir", coordinator_dir,
            "--process-index", str(process_index),
            "--process-count", str(process_count),
            "--grad-microbatches", str(grad_microbatches),
            "--checkpoint-dir", checkpoint_dir,
            "--result", result,
            "--collective-timeout", str(collective_timeout_s)]
    if steps is not None:
        argv += ["--steps", str(steps)]
    if trace:
        argv += ["--trace", trace]
    if sigkill_at_step is not None:
        argv += ["--sigkill-at-step", str(sigkill_at_step)]
    if sigterm_at_step is not None:
        argv += ["--sigterm-at-step", str(sigterm_at_step)]
    if kill_during_save_step is not None:
        argv += ["--kill-during-save-step", str(kill_during_save_step)]
    return argv


def _install_torn_save_kill(trainer, step: int):
    """Arms the torn-commit drill: the save for ``step`` writes a garbage
    tmp shard (a torn write, as a real SIGKILL mid-``np.savez`` would leave)
    and then SIGKILLs the process before the atomic rename."""
    import signal

    ckpt = trainer.checkpointer
    orig = ckpt._write_step

    def torn(save_step, staged, all_keys, aux, commit_timeout_s=None):
        if save_step == step:
            cfg = ckpt.config
            step_dir = os.path.join(cfg.directory, f"step_{save_step:08d}")
            os.makedirs(step_dir, exist_ok=True)
            tmp = os.path.join(
                step_dir, f"shard_{cfg.process_index}.npz.tmp.npz")
            with open(tmp, "wb") as f:
                f.write(b"torn-mid-write")
            os.kill(os.getpid(), signal.SIGKILL)
        return orig(save_step, staged, all_keys, aux,
                    commit_timeout_s=commit_timeout_s)

    ckpt._write_step = torn


def run_worker(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="repro.launch.distributed")
    ap.add_argument("--builder",
                    default="repro.launch.distributed:build_tiny_fleet_config")
    ap.add_argument("--builder-kwargs", default="{}")
    ap.add_argument("--coordinator-dir", required=True)
    ap.add_argument("--process-index", type=int, required=True)
    ap.add_argument("--process-count", type=int, required=True)
    ap.add_argument("--grad-microbatches", type=int, default=0)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--result", default="")
    ap.add_argument("--collective-timeout", type=float, default=60.0)
    ap.add_argument("--trace", default="",
                    help="Chrome trace-event JSON path for this rank "
                         "(pid lane = process index; the supervisor merges "
                         "the per-rank files into one fleet trace)")
    ap.add_argument("--backend", default="file")
    ap.add_argument("--coordinator-address", default="")
    ap.add_argument("--sigkill-at-step", type=int, default=None)
    ap.add_argument("--sigterm-at-step", type=int, default=None)
    ap.add_argument("--kill-during-save-step", type=int, default=None)
    args = ap.parse_args(argv)

    import signal

    from repro.checkpoint.checkpointer import Checkpointer
    from repro.runtime.goodput import GoodputMonitor
    from repro.runtime.signals import Preempted, install_preemption_handler
    from repro.trainer.mesh_rules import ElasticModifier

    cfg = _resolve_builder(args.builder)(**json.loads(args.builder_kwargs))
    if args.checkpoint_dir:
        if cfg.checkpointer is None:
            cfg.checkpointer = Checkpointer.default_config()
        cfg.checkpointer.set(directory=args.checkpoint_dir)
    cfg = ElasticModifier.default_config().set(
        coordinator_dir=args.coordinator_dir,
        process_index=args.process_index,
        process_count=args.process_count,
        grad_microbatches=args.grad_microbatches,
        collective_timeout_s=args.collective_timeout,
        backend=args.backend,
        coordinator_address=args.coordinator_address,
    ).instantiate().apply(cfg)

    if args.trace:
        from repro.observability.runtime import ObservabilityConfig

        # Per-rank span trace on the rank's own pid lane; wall-clock
        # timebase, so the supervisor's merge lands all ranks on one axis.
        cfg.observability = ObservabilityConfig(
            trace_path=args.trace, rank=args.process_index, mfu=False)

    trainer = cfg.instantiate()
    install_preemption_handler(trainer.preemption_event)
    if args.kill_during_save_step is not None:
        _install_torn_save_kill(trainer, args.kill_during_save_step)

    out = open(args.result, "w") if args.result else None

    def emit(record: dict):
        if out is not None:
            out.write(json.dumps(record) + "\n")
            out.flush()

    monitor = GoodputMonitor(
        sink=lambda e: emit({"kind": "event", **{
            k: v for k, v in e.items() if isinstance(
                v, (int, float, str, bool, type(None)))}}))

    def hook(*, step, state, metrics, trainer=trainer, **_):
        emit({"kind": "step", "step": step,
              "loss": float(metrics["loss"])})
        if args.sigterm_at_step is not None and step == args.sigterm_at_step:
            trainer.preemption_event.set()
        if args.sigkill_at_step is not None and step == args.sigkill_at_step:
            os.kill(os.getpid(), signal.SIGKILL)

    try:
        result = trainer.run(args.steps, monitor=monitor, step_hook=hook)
    except Preempted as e:
        emit({"kind": "preempted", "step": e.step,
              "committed": e.committed})
        if out is not None:
            out.close()
        return 143
    except BaseException as e:  # noqa: BLE001 — exit code is the contract
        emit({"kind": "error", "error": repr(e)})
        if out is not None:
            out.close()
        raise
    emit({"kind": "final",
          "input_state": result.get("input_state"),
          "goodput": result["goodput"],
          "num_params": result["num_params"]})
    if out is not None:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(run_worker())
