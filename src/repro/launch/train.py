"""End-to-end training launcher: ``--arch`` selects any assigned architecture.

Usage (reduced model unless --full):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b \
      --steps 200 --batch 8 --seq 64

Mesh rules pick per-instance-type settings exactly as the paper's App. A;
the instance type defaults to the attached device (``tpu-v5e-1``,
``tpu-v5e-4``, ``cpu-local``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import jax

from repro.configs import registry
from repro.core.compile_cache import configure_compile_cache
from repro.core.config import config_for_function
from repro.trainer import optimizers as opt_lib
from repro.layers.base import bf16_policy
from repro.memopt.modifier import MemoryModifier
from repro.quantization.modifier import QuantizationModifier
from repro.trainer.mesh_rules import (
    DtypePolicyModifier,
    GradAccumModifier,
    KernelModifier,
    MeshShapeModifier,
    RematPolicyModifier,
    Zero1Modifier,
    apply_mesh_rules,
)
from repro.trainer.trainer import SpmdTrainer
from repro.checkpoint.checkpointer import Checkpointer
from repro.runtime.signals import Preempted, install_preemption_handler

# Paper App. A-style mesh rules: instance type -> config modifiers. The TPU
# rule is the whole production mixed-precision training recipe — bf16
# compute with fp32 masters, ZeRO-1 optimizer sharding, differentiable
# Pallas flash attention as the training kernel plus a per-hardware tiling
# table — in ~10 lines of config, zero model-code changes (§4.2). Kernel
# choices rewrite the one KernelConfig every kernel-calling layer shares;
# anything left on "auto" resolves per-platform via the kernel registry's
# capability predicates. Rules are anchored fullmatch: list specific
# instance types before broad families.


def _tpu_v5e_rule(*extra):
    """The v5e recipe plus ``extra`` modifiers. The (data, model) mesh takes
    every chip present on the data axis — (1, 1) on one chip, (4, 1) on a
    2x2 host — so one rule fits any slice."""
    return [
        MeshShapeModifier.default_config().set(
            mesh_shape=(-1, 1), mesh_axis_names=("data", "model")),
        RematPolicyModifier.default_config().set(policy="full"),
        KernelModifier.default_config().set(
            op_overrides={"attention.fwd": "pallas"},
            update={"block_q": 256, "block_k": 512}),  # v5e tiling table
        DtypePolicyModifier.default_config().set(policy=bf16_policy()),
        Zero1Modifier.default_config(),
        *extra,
    ]


MESH_RULES = [
    # Low-precision variants ride the same recipe plus ONE extra modifier:
    # "-fp8" suffix = fp8 train compute (delayed-scaling fake-quant at
    # module boundaries, fp32 masters kept by ZeRO-1 as usual); "-w8a8"
    # suffix = int8 weight/activation GEMMs.
    ("tpu-v5e-.*-fp8", _tpu_v5e_rule(
        QuantizationModifier.default_config().set(fp8=True))),
    # Memory-frugal variants (paper §4.2 applied to training memory):
    # "-frugal" = bf16 Adam EMA buffers + reversible residual stacks (2x
    # smaller moments, O(1)-in-depth activations); "-frugal-max" = Adafactor
    # factored second moments + reversible (optimizer state drops from 8
    # bytes/param to O(n+m) per matrix).
    ("tpu-v5e-.*-frugal-max", _tpu_v5e_rule(
        MemoryModifier.default_config().set(
            optimizer="adafactor", reversible=True))),
    ("tpu-v5e-.*-frugal", _tpu_v5e_rule(
        MemoryModifier.default_config().set(
            state_dtype="bf16", reversible=True))),
    ("tpu-v5e-.*-w8a8", _tpu_v5e_rule(
        QuantizationModifier.default_config().set(w8a8=True))),
    ("tpu-v5e-.*", _tpu_v5e_rule()),
    ("cpu-.*", [
        MeshShapeModifier.default_config().set(
            mesh_shape=(1,), mesh_axis_names=("data",)),
        RematPolicyModifier.default_config().set(policy=None),
        KernelModifier.default_config().set(
            op_overrides={"attention.fwd": "ref"}),
    ]),
]

# Instance family of each attached device, keyed by ``device_kind`` as JAX
# reports it.
_TPU_FAMILIES = {"TPU v5 lite": "tpu-v5e"}


def attached_instance_type() -> str:
    """The instance type of the devices this process sees, e.g.
    ``tpu-v5e-4`` for a 2x2 v5e host or ``cpu-local``. An accelerator with
    no mesh rule is an error, never a silent CPU recipe."""
    devices = jax.devices()
    if devices[0].platform == "cpu":
        return "cpu-local"
    kind = devices[0].device_kind
    if kind not in _TPU_FAMILIES:
        raise ValueError(f"no mesh rule for device kind {kind!r} "
                         f"(known: {sorted(_TPU_FAMILIES)})")
    return f"{_TPU_FAMILIES[kind]}-{len(devices)}"


def build_trainer_config(arch: str, *, full: bool, steps: int, batch: int,
                         seq: int, lr: float, instance_type: str,
                         checkpoint_dir: str = ""):
    spec = registry.get_spec(arch)
    model_cfg = spec.make_model() if full else spec.make_smoke()
    cfg = SpmdTrainer.default_config().set(
        name="trainer", model=model_cfg, max_steps=steps, log_every_n=10,
        seed=0)
    task = {"audio": "audio", "vlm": "vlm"}.get(spec.modality, "lm")
    vocab = model_cfg.decoder.vocab_size
    dim = model_cfg.decoder.dim
    cfg.input.set(task=task, vocab_size=vocab, seq_len=seq,
                  global_batch_size=batch, model_dim=dim, num_patches=4)
    cfg.learner.optimizer = config_for_function(opt_lib.adamw).set(
        learning_rate=config_for_function(opt_lib.linear_warmup_cosine).set(
            peak_lr=lr, warmup_steps=max(steps // 20, 1), total_steps=steps),
        weight_decay=0.01)
    if checkpoint_dir:
        cfg.checkpointer = Checkpointer.default_config().set(
            directory=checkpoint_dir)
        cfg.checkpoint_every_n = max(steps // 4, 1)
    cfg = apply_mesh_rules(cfg, instance_type=instance_type, rules=MESH_RULES)
    return cfg


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=registry.ALL_ARCHS)
    ap.add_argument("--full", action="store_true",
                    help="full paper-size config (needs a real cluster)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--instance-type", default="auto",
                    help="mesh-rule key; 'auto' names the attached device "
                         "(e.g. tpu-v5e-1, cpu-local)")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    configure_compile_cache()
    instance_type = args.instance_type
    if instance_type == "auto":
        instance_type = attached_instance_type()
    cfg = build_trainer_config(
        args.arch, full=args.full, steps=args.steps, batch=args.batch,
        seq=args.seq, lr=args.lr, instance_type=instance_type,
        checkpoint_dir=args.checkpoint_dir)
    trainer = cfg.instantiate()
    # Preemption wiring (§5): a scheduler SIGTERM sets the event; the loop
    # commits a synchronous emergency checkpoint at the next step boundary
    # and raises Preempted — restarting the same command resumes exactly.
    install_preemption_handler(trainer.preemption_event)
    try:
        result = trainer.run()
    except Preempted as e:
        print(f"[train] preempted at step {e.step}; "
              + ("emergency checkpoint committed — rerun to resume"
                 if e.committed else "no checkpointer configured"))
        sys.exit(143)  # 128 + SIGTERM, like a default-handled TERM
    print(f"[train] arch={args.arch} params={result['num_params']:,}")
    for row in result["history"]:
        print(f"[train] step={row['step']:>5} loss={row['loss']:.4f} "
              f"acc={row.get('accuracy', 0):.3f} "
              f"steps/s={row['steps_per_s']:.2f}")
    g = result["goodput"]
    buckets = " ".join(f"{k}={v:.2f}s"
                       for k, v in sorted(g["buckets"].items()))
    # Raw goodput on short runs is dominated by one-time compile/init;
    # steady excludes those startup buckets — the sustainable number.
    print(f"[train] goodput={g['goodput_fraction']:.3f} "
          f"steady={g['steady_goodput_fraction']:.3f} "
          f"wall={g['wall_s']:.2f}s {buckets}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"history": result["history"],
                       "goodput": result["goodput"]}, f, indent=1)


if __name__ == "__main__":
    main()
