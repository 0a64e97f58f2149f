"""Paper Table 3: training performance.

Wall-clock MFU on real accelerators is out of scope for this CPU container;
this benchmark reports (a) measured CPU step time + tokens/s on the reduced
per-family models (regression tracking across the whole substrate: data ->
model -> grads -> optimizer), with the train step pre-compiled so step time
is warm, (b) an XLA-derived peak-HBM proxy per arch (argument + temp +
output bytes of the compiled train step), (c) fp32 vs bf16-dtype-policy step
time / loss parity on a subset of archs, and (d) the roofline-derived
step-time bound for the paper-size models from AOT dry-run records when
available (EXPERIMENTS.md §Roofline holds the full table).

``run.py`` persists ``LAST_JSON`` as ``BENCH_train.json`` so the training
perf trajectory is tracked across PRs.
"""

import glob
import json
import os
import time

import jax

from repro.configs import registry
from repro.core.config import config_for_function
from repro.observability.hardware import estimate_mfu
from repro.trainer import optimizers as opt_lib
from repro.trainer.trainer import SpmdTrainer

BENCH_ARCHS = ["qwen2-1.5b", "mixtral-8x7b", "jamba-1.5-large-398b",
               "rwkv6-7b", "hubert-xlarge"]
# Archs additionally benchmarked under the bf16 dtype policy (fp32 parity
# tolerance documented in README "Training path").
BF16_ARCHS = ["qwen2-1.5b", "rwkv6-7b"]
# Arch for the fp8-vs-bf16 delayed-scaling parity run (README
# "Low-precision end-to-end"; acceptance: <1% relative loss diff).
FP8_PARITY_ARCH = "qwen2-1.5b"
FP8_PARITY_STEPS = 60
# Memory ablation (README "Memory-frugal training"): one arch, deepened to
# MEMOPT_DEPTH (activation memory is the depth-scaling term reversible
# blocks remove), fixed global batch, 60-step loss parity columns.
MEMOPT_ARCH = "qwen2-1.5b"
MEMOPT_STEPS = 60
MEMOPT_DEPTH = 8
# name -> (MemoryModifier kwargs, peak_lr override). LR is a per-optimizer-
# family tuning constant (Adafactor/SM3 take ~10-100x Adam's LR), not part
# of the memory ablation itself.
MEMOPT_CONFIGS = [
    ("adamw", None, None),
    ("adamw-bf16-state", {"state_dtype": "bf16"}, None),
    ("adamw-int8-state", {"state_dtype": "int8"}, None),
    ("adafactor", {"optimizer": "adafactor"}, 1e-2),
    ("sm3", {"optimizer": "sm3"}, 1e-1),
    ("reversible", {"reversible": True}, None),
]

LAST_JSON = None


def _make_trainer(arch, *, policy=None, fp8=False, memopt=None, depth=None,
                  lr=1e-3, steps=8, batch=8, seq=32):
    spec = registry.get_spec(arch)
    model_cfg = spec.make_smoke()
    if depth is not None:
        from repro.core.config import update_configs_recursively

        update_configs_recursively(model_cfg, {"num_layers": depth})
    cfg = SpmdTrainer.default_config().set(
        name="t", model=model_cfg, max_steps=steps, log_every_n=steps)
    task = {"audio": "audio", "vlm": "vlm"}.get(spec.modality, "lm")
    cfg.input.set(task=task, vocab_size=model_cfg.decoder.vocab_size,
                  seq_len=seq, global_batch_size=batch,
                  model_dim=model_cfg.decoder.dim, num_patches=4)
    cfg.learner.optimizer = config_for_function(opt_lib.adamw).set(peak_lr=lr)
    if policy is not None:
        from repro.trainer.mesh_rules import DtypePolicyModifier

        modifier = DtypePolicyModifier.default_config().set(
            policy=policy).instantiate()
        cfg = modifier.apply(cfg)
    if fp8:
        from repro.quantization.modifier import QuantizationModifier

        cfg = QuantizationModifier.default_config().set(
            fp8=True).instantiate().apply(cfg)
    if memopt is not None:
        from repro.memopt.modifier import MemoryModifier

        cfg = MemoryModifier.default_config().set(
            **memopt).instantiate().apply(cfg)
    return cfg.instantiate()


def _train_bench(arch, *, policy=None, fp8=False, steps=8, batch=8, seq=32):
    trainer = _make_trainer(arch, policy=policy, fp8=fp8, steps=steps,
                            batch=batch, seq=seq)
    t0 = time.perf_counter()
    trainer.run(num_steps=1)  # compile + warm (the jitted step is cached)
    first_run = time.perf_counter() - t0
    t0 = time.perf_counter()
    result = trainer.run(num_steps=steps)
    wall = time.perf_counter() - t0
    per_step = wall / steps
    # Compiled-step cost: flops, bytes_accessed, peak_hbm_proxy_bytes.
    cost = trainer.step_cost_analysis()
    n_dev = max(len(jax.devices()), 1)
    mfu = estimate_mfu(cost.get("flops"), per_step, num_devices=n_dev)
    return {
        # Warm, steady-state step time: the trainer's engine-cached jit means
        # the step compiles exactly once per process (incl. resume), so this
        # — not the compile-inflated first run — is what repeats at scale.
        "step_us": per_step * 1e6,
        "first_run_us_incl_compile": first_run * 1e6,
        "tokens_per_s": batch * seq / per_step,
        "tokens_per_s_per_device": batch * seq / per_step / n_dev,
        "step_flops": cost.get("flops"),
        # Achieved/peak model FLOP/s of the device; None on the CPU, which
        # has no device peak.
        "mfu": mfu,
        "num_params": int(result["num_params"]),
        "peak_hbm_proxy_bytes": cost["peak_hbm_proxy_bytes"],
        "final_loss": float(result["final"]["loss"]),
    }


def _memopt_bench(rows):
    """Memory-frugal training ablation (README "Memory-frugal training").

    One arch at depth MEMOPT_DEPTH, fixed global batch/seq, MEMOPT_STEPS
    steps per config. Tracked columns per config: exact optimizer state
    bytes (``train/opt_state_bytes`` accounting), XLA peak-HBM proxy of the
    compiled step, and 60-step final loss vs the fp32 adamw baseline. The
    memory ratios are backend-independent (dtype/shape arithmetic); the
    loss-parity column is the numerics signal.
    """
    out = {"arch": MEMOPT_ARCH, "depth": MEMOPT_DEPTH, "steps": MEMOPT_STEPS,
           "configs": {}}
    base = None
    for name, mod, lr in MEMOPT_CONFIGS:
        trainer = _make_trainer(
            MEMOPT_ARCH, memopt=mod, depth=MEMOPT_DEPTH, lr=lr or 1e-3,
            steps=MEMOPT_STEPS, batch=8, seq=64)
        trainer.run(num_steps=1)  # compile + warm
        t0 = time.perf_counter()
        result = trainer.run(num_steps=MEMOPT_STEPS)
        per_step = (time.perf_counter() - t0) / MEMOPT_STEPS
        cost = trainer.step_cost_analysis()
        entry = {
            "opt_state_bytes": int(result["opt_state_bytes"]),
            "peak_hbm_proxy_bytes": cost["peak_hbm_proxy_bytes"],
            "final_loss": float(result["final"]["loss"]),
            "step_us": per_step * 1e6,
        }
        if base is None:
            base = entry
        else:
            entry["opt_bytes_ratio_vs_adamw"] = (
                base["opt_state_bytes"] / max(entry["opt_state_bytes"], 1))
            entry["hbm_ratio_vs_adamw"] = (
                entry["peak_hbm_proxy_bytes"]
                / max(base["peak_hbm_proxy_bytes"], 1))
            entry["loss_rel_diff_vs_adamw"] = (
                abs(entry["final_loss"] - base["final_loss"])
                / max(abs(base["final_loss"]), 1e-9))
        out["configs"][name] = entry
        detail = (f"opt_bytes={entry['opt_state_bytes']};"
                  f"peak_hbm_proxy={entry['peak_hbm_proxy_bytes']};"
                  f"loss={entry['final_loss']:.4f}")
        if base is not entry:
            detail += (f";opt_shrink={entry['opt_bytes_ratio_vs_adamw']:.1f}x;"
                       f"loss_rel_diff={entry['loss_rel_diff_vs_adamw']:.4f}")
        rows.append((f"train_memopt/{name}", entry["step_us"], detail))
    return out


def _fleet_bench(*, world=2, steps=6):
    """Elastic multi-process goodput: a real 2-worker fleet (subprocess
    workers on the CPU, file-backed collectives) trained to completion;
    reports the aggregated fleet goodput. A failing fleet raises."""
    import shutil
    import tempfile

    from repro.runtime.supervisor import FleetSupervisor

    wd = tempfile.mkdtemp(prefix="bench_fleet_")
    try:
        sup = FleetSupervisor(
            wd, schedule=(world,), steps=steps, grad_microbatches=world,
            builder_kwargs={"steps": steps, "checkpoint_every_n": steps})
        res = sup.run()
        g = res["goodput"]
        return {
            "world_size": world,
            "steps": steps,
            "fleet_goodput_fraction": g["fleet_goodput_fraction"],
            "fleet_steady_goodput_fraction":
                g["fleet_steady_goodput_fraction"],
            "rank_seconds": g["rank_seconds"],
            "productive_s": g["productive_s"],
        }
    finally:
        shutil.rmtree(wd, ignore_errors=True)


def run():
    global LAST_JSON
    rows = []
    archs_json = {}
    for arch in BENCH_ARCHS:
        fp32 = _train_bench(arch)
        archs_json[arch] = {"fp32": fp32}
        mfu_str = (f"{fp32['mfu']:.4f}" if fp32["mfu"] is not None
                   else "n/a")
        rows.append((f"train_step/{arch}", fp32["step_us"],
                     f"tokens_per_s={fp32['tokens_per_s']:.0f};"
                     f"mfu={mfu_str};"
                     f"peak_hbm_proxy={fp32['peak_hbm_proxy_bytes']};"
                     f"params={fp32['num_params']}"))
        if arch in BF16_ARCHS:
            from repro.layers.base import bf16_policy

            bf16 = _train_bench(arch, policy=bf16_policy())
            loss_rel = abs(bf16["final_loss"] - fp32["final_loss"]) / \
                max(abs(fp32["final_loss"]), 1e-9)
            bf16["loss_rel_diff_vs_fp32"] = loss_rel
            bf16["step_speedup_vs_fp32"] = fp32["step_us"] / bf16["step_us"]
            bf16["hbm_ratio_vs_fp32"] = (bf16["peak_hbm_proxy_bytes"]
                                         / max(fp32["peak_hbm_proxy_bytes"], 1))
            if jax.default_backend() == "cpu":
                # The loss-parity number is the tracked signal here: this
                # container's CPU backend EMULATES bf16 (upcasts every op),
                # so wall-clock/bytes do not reflect accelerator behaviour.
                bf16["note"] = ("cpu backend emulates bf16; speedup/HBM "
                                "ratios are not meaningful off-accelerator")
            archs_json[arch]["bf16"] = bf16
            rows.append((f"train_step_bf16/{arch}", bf16["step_us"],
                         f"speedup={bf16['step_speedup_vs_fp32']:.2f}x;"
                         f"hbm_ratio={bf16['hbm_ratio_vs_fp32']:.2f};"
                         f"loss_rel_diff={loss_rel:.4f}"))
    # Roofline-bound step times from dry-run records (paper-size models).
    roofline = {}
    for path in sorted(glob.glob("experiments/dryrun/*__train_4k__single.json")):
        with open(path) as f:
            rec = json.load(f)
        if rec.get("status") != "ok" or "roofline" not in rec:
            continue
        r = rec["roofline"]
        bound_s = max(r["compute_s"], r["memory_s"], r["collective_s"])
        mfu_bound = r["model_flops_global"] / (
            rec["chips"] * 197e12 * bound_s) if bound_s else 0
        roofline[rec["arch"]] = {"bound_us": bound_s * 1e6,
                                 "dominant": r["dominant"],
                                 "mfu_bound": mfu_bound}
        rows.append((f"train_roofline_bound/{rec['arch']}", bound_s * 1e6,
                     f"dominant={r['dominant']};mfu_bound={mfu_bound:.3f}"))
    # fp8 delayed-scaling parity: bf16 policy vs bf16 + fp8 boundaries on
    # one arch over a longer horizon (amax histories need steps to settle).
    # Loss parity is the tracked signal — CPU emulates the fp8 casts, so
    # only numerics (not wall-clock) are meaningful here.
    from repro.layers.base import bf16_policy

    base = _train_bench(FP8_PARITY_ARCH, policy=bf16_policy(),
                        steps=FP8_PARITY_STEPS)
    fp8 = _train_bench(FP8_PARITY_ARCH, policy=bf16_policy(), fp8=True,
                       steps=FP8_PARITY_STEPS)
    loss_rel = abs(fp8["final_loss"] - base["final_loss"]) / \
        max(abs(base["final_loss"]), 1e-9)
    fp8_json = {
        "arch": FP8_PARITY_ARCH,
        "steps": FP8_PARITY_STEPS,
        "bf16_final_loss": base["final_loss"],
        "fp8_final_loss": fp8["final_loss"],
        "loss_rel_diff_vs_bf16": loss_rel,
        "step_us_bf16": base["step_us"],
        "step_us_fp8": fp8["step_us"],
    }
    rows.append((f"train_fp8_parity/{FP8_PARITY_ARCH}", fp8["step_us"],
                 f"steps={FP8_PARITY_STEPS};"
                 f"loss_rel_diff_vs_bf16={loss_rel:.4f}"))
    memopt_json = _memopt_bench(rows)
    LAST_JSON = {"archs": archs_json, "roofline": roofline,
                 "fp8_train_parity": fp8_json, "memopt": memopt_json}
    fleet = _fleet_bench()
    LAST_JSON["fleet"] = fleet
    rows.append((
        "train_fleet_goodput", fleet["rank_seconds"] * 1e6,
        f"world={fleet['world_size']};"
        f"goodput={fleet['fleet_goodput_fraction']:.3f};"
        f"steady={fleet['fleet_steady_goodput_fraction']:.3f}"))
    return rows
