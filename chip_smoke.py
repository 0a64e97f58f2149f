"""Chip smoke test: the trainer and the paged server on one TPU v5e chip.

Drives the system's two main paths through the entry points a user calls,
at the published widths of qwen2-1.5b (d=1536, 12 query / 2 KV heads of
128, d_ff=8960, vocabulary 151936), with random weights from ``--seed``:

  * train: ``launch.train.build_trainer_config(full=True)`` under the v5e
    mesh rule (bf16 compute, Pallas flash attention forward and backward,
    ZeRO-1), depth cut to 4 of 28 layers, 8 steps at global batch 4 x 1024;
    the loss and gradient norm of the first 3 steps are checked against the
    same steps with ``attention.fwd`` forced to the XLA reference (the
    gradient norms carry the flash backward, the later losses its updates);
  * serve: all 28 layers with the paged KV cache, through
    ``InferenceEngine`` + ``ServingGateway``: 8 seeded requests of 64-1024
    prompt tokens and 32 new tokens, greedy and sampled; every greedy
    request is checked against the same engine with ``attention.decode``
    forced to the reference: its tokens may part only at a tie, and with
    the Pallas engine's tokens fed to both (teacher forcing), the logits of
    every decode step must agree.

Both phases check which backend the kernel registry resolved and print the
device's peak memory. Any failed check exits non-zero; the last line of a
passing run is one JSON object naming the device.

``--chips 4`` runs only the training comparison across chips: the same
depth-cut trainer on a (4, 1) data mesh with ZeRO-1 and on one chip, whose
first losses must agree, and whose parameter and optimizer-state arrays
must have shards on all four devices.

Run from the repository root: ``python chip_smoke.py [--chips 4]``. It needs
a TPU; on any other platform it exits non-zero without a result.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.core.compile_cache import configure_compile_cache  # noqa: E402

ARCH = "qwen2-1.5b"
TRAIN_LAYERS = 4  # of 28: depth cut to fit optimizer state + activations
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS, TRAIN_LR = 4, 1024, 8, 1e-4
# Pallas flash attention vs the XLA reference, both bf16, over the first
# REF_STEPS steps: relative limits on the loss and the gradient norm, set
# from chip readings (PERF.md). Sound runs differ by at most 1.11e-05
# (loss) and 1.11e-04 (gradient norm); the flash backward with dq scaled
# by 0.9 moves the gradient norm by 1.33e-02, with dk and dv so scaled by
# 6.25e-02.
REF_STEPS = 3
TRAIN_REL_TOL = {"loss": 1e-4, "grad_norm": 1e-3}
# Four chips vs one: same math, different reduction order of the
# data-parallel gradient sum in bf16.
MULTICHIP_STEPS, MULTICHIP_REL_TOL = 3, 1e-2
SERVE_REQUESTS, SERVE_NEW_TOKENS, SERVE_SLOTS = 8, 32, 8
SERVE_MAX_LEN, PAGE_SIZE, PREFILL_CHUNK = 1088, 16, 256
MAX_PROMPT = 1024
# Greedy tokens of the Pallas and reference decode may part only where the
# two chosen tokens' logits tie within bf16 rounding: four bf16 steps at
# logits of 2 to 4 (sound readings: gaps up to 0.0469).
TIE_TOL = 0.0625
# Teacher-forced decode, Pallas vs reference: max |logit difference| over
# every step and the whole vocabulary, from chip readings (PERF.md): sound
# runs read at most 0.0664; a kernel that masks out the newest token reads
# 0.2109 and more.
LOGIT_TOL = 0.125


def log(msg: str):
    print(msg, flush=True)


def check(ok, msg: str):
    """A failed check ends the run with a non-zero exit and no result."""
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def peak_bytes(jax) -> int:
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def resolutions(registry, op: str, predicate):
    """Backends the registry resolved for ``op`` at call sites whose
    features satisfy ``predicate``."""
    return sorted({backend for o, _, feats, backend
                   in registry.dispatch_cache_stats()["resolved"]
                   if o == op and predicate(feats)})


def print_resolutions(registry, phase: str):
    for op, requested, feats, backend in \
            registry.dispatch_cache_stats()["resolved"]:
        flags = ",".join(f.name for f in dataclasses.fields(feats)
                         if getattr(feats, f.name) is True)
        log(f"[{phase}] resolved {op} (requested {requested}; "
            f"{feats.dtype}; {flags or '-'}) -> {backend}")


def train_config(*, steps: int, instance_type: str, seed: int,
                 fwd_backend: str = ""):
    from repro.launch.train import build_trainer_config
    from repro.trainer.mesh_rules import KernelModifier

    cfg = build_trainer_config(
        ARCH, full=True, steps=steps, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
        lr=TRAIN_LR, instance_type=instance_type)
    cfg.model.decoder.stack.set(num_layers=TRAIN_LAYERS)
    cfg.set(seed=seed, log_every_n=1)
    cfg.input.set(seed=seed)
    if fwd_backend:
        cfg = KernelModifier.default_config().set(
            op_overrides={"attention.fwd": fwd_backend}).instantiate(
            ).apply(cfg)
    return cfg


def losses_of(result):
    return [row["loss"] for row in result["history"]]


def compare_to_ref(history, ref_history):
    """Per step and metric, the relative difference of the Pallas run from
    the reference run, checked against ``TRAIN_REL_TOL``."""
    for step, (row, ref) in enumerate(zip(history, ref_history)):
        for key, tol in TRAIN_REL_TOL.items():
            rel = abs(row[key] - ref[key]) / abs(ref[key])
            log(f"[train] step {step} {key} pallas {row[key]:.6f} vs ref "
                f"{ref[key]:.6f}: rel diff {rel:.2e} (tol {tol})")
            check(rel <= tol, f"flash attention {key} disagrees with ref")


def train_phase(jax, seed: int):
    from repro.kernels import registry
    from repro.launch.train import attached_instance_type

    instance = attached_instance_type()
    log(f"[train] {ARCH} under mesh rule {instance}: published widths, "
        f"depth cut to {TRAIN_LAYERS} of 28 layers, global batch "
        f"{TRAIN_BATCH} x seq {TRAIN_SEQ}, {TRAIN_STEPS} steps")
    registry.clear_dispatch_cache()
    trainer = train_config(steps=TRAIN_STEPS, instance_type=instance,
                           seed=seed).instantiate()
    t0 = time.perf_counter()
    result = trainer.run()
    wall = time.perf_counter() - t0
    history = result["history"]
    losses = losses_of(result)
    for row in history:
        log(f"[train] step {row['step']} loss {row['loss']:.6f} grad_norm "
            f"{row['grad_norm']:.6f}")
    log(f"[train] {result['num_params']:,} params; {wall:.1f}s for "
        f"{TRAIN_STEPS} steps incl. compile (smoke timing, not a "
        "measurement)")
    print_resolutions(registry, "train")
    fwd = resolutions(registry, "attention.fwd", lambda f: f.needs_grad)
    check(fwd == ["pallas"], f"training attention.fwd resolved to {fwd}")
    check(len(losses) == TRAIN_STEPS and all(map(math.isfinite, losses)),
          f"non-finite or missing losses: {losses}")
    del trainer, result
    gc.collect()

    ref = train_config(steps=REF_STEPS, instance_type=instance, seed=seed,
                       fwd_backend="ref").instantiate()
    ref_history = ref.run()["history"]
    check(len(ref_history) == REF_STEPS, "missing reference steps")
    compare_to_ref(history, ref_history)
    del ref
    gc.collect()
    log(f"[train] peak_bytes_in_use {peak_bytes(jax)}")


def serve_engine(decode_backend: str = ""):
    import jax.numpy as jnp

    from repro.configs import registry as arch_registry
    from repro.core.config import update_configs_recursively, visit_config
    from repro.inference.engine import InferenceEngine
    from repro.layers.base import DtypePolicy

    cfg = arch_registry.get_spec(ARCH).make_model()
    # A server holds bf16 weights and computes in bf16.
    update_configs_recursively(cfg, {"dtype_policy": DtypePolicy().set(
        param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16)})
    num_pages = 1 + SERVE_SLOTS * -(-SERVE_MAX_LEN // PAGE_SIZE)

    def to_paged(_, c):
        if getattr(c, "kv_cache_layout", None) == "dense":
            c.set(kv_cache_layout="paged", page_size=PAGE_SIZE,
                  num_pages=num_pages)
        if decode_backend and "op_overrides" in c.keys():
            c.set(op_overrides={"attention.decode": decode_backend})

    visit_config(cfg, to_paged)
    return InferenceEngine.default_config().set(
        name="engine", model=cfg, max_len=SERVE_MAX_LEN,
        slots=SERVE_SLOTS).instantiate()


def serve_requests(seed: int, vocab: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    out = []
    for i in range(SERVE_REQUESTS):
        n = 64 * int(rng.integers(1, 17))  # 64..1024 tokens
        prompt = rng.integers(1, vocab, size=(n,)).astype(np.int32)
        greedy = i % 2 == 0
        out.append((prompt, 0.0 if greedy else 0.8, 0 if greedy else 50))
    return out


def serve(engine, requests):
    from repro.serving import SamplingParams, ServingGateway

    gateway = ServingGateway(engine, prefill_chunk=PREFILL_CHUNK)
    rids = [gateway.submit(prompt, sampling=SamplingParams(
        max_new_tokens=SERVE_NEW_TOKENS, temperature=temp, top_k=top_k))
        for prompt, temp, top_k in requests]
    t0 = time.perf_counter()
    results = gateway.drain()
    wall = time.perf_counter() - t0
    return [results[r].tokens for r in rids], gateway.metrics(), wall


def teacher_forced(engine):
    """A jitted ``(params, prompt (1, MAX_PROMPT), length, tokens (N,)) ->
    logits (N, V)``: the prompt's prefill, then one decode step per given
    token, as the engine's own programs run them. Row i holds the logits
    that choose generated token i."""
    import jax
    import jax.numpy as jnp

    from repro.core.module import functional

    model, max_len = engine.model, engine.config.max_len

    def run(params, prompt, length, tokens):
        cache, _ = functional(model, state=params, inputs=(1, max_len),
                              method="init_states")
        (cache, logits), _ = functional(
            model, state=params, method="prefill",
            inputs={"state": cache, "input_ids": prompt, "length": length})
        first = jax.lax.dynamic_index_in_dim(logits[0], length - 1,
                                             keepdims=True)

        def step(cache, token):
            (cache, logits), _ = functional(
                model, state=params, method="extend_step",
                inputs={"state": cache, "ids_step": token.reshape(1, 1)})
            return cache, logits[0, -1]

        _, rest = jax.lax.scan(step, cache, tokens[:-1])
        return jnp.concatenate([first, rest]).astype(jnp.float32)

    return jax.jit(run)


def forced_logits(engine, fn, prompt, tokens):
    import numpy as np

    padded = np.zeros((1, MAX_PROMPT), np.int32)
    padded[0, :len(prompt)] = prompt
    return np.asarray(fn(engine._params, padded, np.int32(len(prompt)),
                         np.asarray(tokens, np.int32)))


def serve_phase(jax, seed: int):
    from repro.kernels import registry

    log(f"[serve] {ARCH}: all 28 layers at published widths, bf16, paged KV "
        f"(page {PAGE_SIZE}), {SERVE_SLOTS} slots, max_len {SERVE_MAX_LEN}")
    registry.clear_dispatch_cache()
    engine = serve_engine()
    requests = serve_requests(seed, engine.config.model.decoder.vocab_size)
    log("[serve] prompts " + " ".join(
        f"{len(p)}{'g' if t == 0 else 's'}" for p, t, _ in requests)
        + f" (g greedy, s sampled), {SERVE_NEW_TOKENS} new tokens each")
    params = jax.jit(engine.model.initialize_parameters_recursively)(
        jax.random.PRNGKey(seed))
    engine.load(params)
    tokens, m, wall = serve(engine, requests)
    log(f"[serve] drained {m['completed']} requests in {wall:.1f}s incl. "
        f"compile; TTFT p50 {m['ttft_p50_s'] * 1e3:.1f}ms p99 "
        f"{m['ttft_p99_s'] * 1e3:.1f}ms, TPOT p50 "
        f"{m['tpot_p50_s'] * 1e3:.2f}ms p99 {m['tpot_p99_s'] * 1e3:.2f}ms, "
        f"{m['tokens_per_s']:.1f} tok/s, accepted/verify step "
        f"{m['accepted_per_step']:.2f} (smoke timings, not measurements)")
    print_resolutions(registry, "serve")
    decode = resolutions(registry, "attention.decode",
                         lambda f: not f.multi_query)
    check(decode == ["pallas"], f"decode step resolved to {decode}")
    check(all(len(t) == SERVE_NEW_TOKENS for t in tokens),
          f"output lengths {[len(t) for t in tokens]}")

    ref_engine = serve_engine(decode_backend="ref")
    ref_engine.load(params)
    ref_tokens, _, _ = serve(ref_engine, requests)
    forced = teacher_forced(engine), teacher_forced(ref_engine)
    for i, ((prompt, temp, _), got, want) in enumerate(
            zip(requests, tokens, ref_tokens)):
        if temp > 0:
            continue
        # Both decode backends, fed the Pallas engine's tokens.
        got_logits, ref_logits = (forced_logits(e, fn, prompt, got)
                                  for e, fn in zip((engine, ref_engine),
                                                   forced))
        diff = float(abs(got_logits - ref_logits).max())
        log(f"[serve] request {i}: teacher-forced decode logits, pallas vs "
            f"ref: max abs diff {diff:.4f} over {len(got)} steps (tol "
            f"{LOGIT_TOL})")
        check(diff <= LOGIT_TOL, f"request {i}: decode logits disagree")
        if got == want:
            log(f"[serve] request {i}: greedy tokens equal the reference")
            continue
        # Up to the first divergence both streams are the same: the
        # reference decode's forced logits there show how near its two
        # candidates are.
        j = next(k for k, (a, b) in enumerate(zip(got, want)) if a != b)
        gap = float(abs(ref_logits[j, got[j]] - ref_logits[j, want[j]]))
        log(f"[serve] request {i}: first divergence at token {j} "
            f"({got[j]} vs {want[j]}), logit gap {gap:.4f} "
            f"(tie tol {TIE_TOL})")
        check(gap <= TIE_TOL, f"request {i} diverges without a tie")
    log(f"[serve] peak_bytes_in_use {peak_bytes(jax)}")


def multichip_phase(jax, seed: int):
    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, have {devices}")
    log(f"[4chip] {ARCH} depth {TRAIN_LAYERS}, global batch {TRAIN_BATCH} x "
        f"{TRAIN_SEQ}: (4, 1) data mesh with ZeRO-1 vs one chip, "
        f"{MULTICHIP_STEPS} steps")
    cfg = train_config(steps=MULTICHIP_STEPS, instance_type="tpu-v5e-4",
                       seed=seed)
    trainer = cfg.instantiate()
    result = trainer.run()
    mesh = trainer.build_mesh()
    log(f"[4chip] mesh {dict(mesh.shape)}")
    for part in ("params", "opt_state"):
        leaves = jax.tree.leaves(result["state"][part])
        devs = {d for leaf in leaves for d in leaf.sharding.device_set}
        split = sum(len({s.index for s in leaf.addressable_shards}) > 1
                    for leaf in leaves)
        log(f"[4chip] {part}: {len(leaves)} arrays on {len(devs)} devices, "
            f"{split} partitioned across them")
        check(len(devs) == 4, f"{part} is not on 4 devices: {devs}")
        check(part == "params" or split, "ZeRO-1 partitioned no opt state")
    four = losses_of(result)
    del trainer, result
    gc.collect()

    one = train_config(steps=MULTICHIP_STEPS, instance_type="tpu-v5e-4",
                       seed=seed)
    one.set(mesh_shape=(1, 1))
    single = one.instantiate()
    check(single.build_mesh().devices.size == 1, "not a one-chip mesh")
    ones = losses_of(single.run())
    for step, (a, b) in enumerate(zip(four, ones)):
        rel = abs(a - b) / abs(b)
        log(f"[4chip] step {step} loss 4 chips {a:.6f} vs 1 chip {b:.6f}: "
            f"rel diff {rel:.2e} (tol {MULTICHIP_REL_TOL})")
        check(rel <= MULTICHIP_REL_TOL, "4-chip loss disagrees with 1 chip")
    check(len(four) == len(ones) == MULTICHIP_STEPS, "missing losses")
    log(f"[4chip] peak_bytes_in_use {peak_bytes(jax)}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cache = configure_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX platform {dev.platform!r}); "
                 "refusing to run on another backend")
    log(f"[device] {dev.device_kind} x {len(jax.devices())}; compile cache "
        f"{cache}")
    if args.chips == 4:
        multichip_phase(jax, args.seed)
    else:
        train_phase(jax, args.seed)
        serve_phase(jax, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
