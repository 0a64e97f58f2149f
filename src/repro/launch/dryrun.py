import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST precede every other import: jax locks the device count on first init.
# The dry-run (and ONLY the dry-run) needs 512 placeholder CPU devices so the
# production meshes can be built. This is the paper's AOT-compilation workflow
# (§4.2): lower + compile the EXACT train/serve codepath on a single host,
# catching sharding errors and OOMs before touching accelerators.

"""Multi-pod AOT dry-run launcher.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1.5b \
      --shape train_4k --mesh single --out experiments/dryrun

For every (architecture x input-shape x mesh):
  * builds the trainer's train_step (train shapes) or the engine's
    prefill/serve_step (prefill/decode shapes),
  * jit(...).lower(ShapeDtypeStructs).compile() against the production mesh,
  * prints memory_analysis() (fits-check) and cost_analysis() (FLOPs/bytes),
  * extracts collective bytes from the optimized HLO,
  * writes a JSON record consumed by EXPERIMENTS.md §Dry-run / §Roofline.
"""

import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.configs.common import SHAPES, MODEL_AXIS
from repro.core.config import config_for_function, visit_config
from repro.core.module import functional
from repro.core.utils import named_sharding, set_mesh
from repro.launch.analysis import V5E, roofline_terms
from repro.launch.mesh import make_production_mesh
from repro.layers.base import ParameterSpec
from repro.trainer import optimizers as opt_lib
from repro.trainer.trainer import SpmdTrainer

# Models whose optimizer state must be host-offloaded on v5e (paper §4.2);
# the CPU backend cannot compile memory-kind annotations, so the dry-run
# reports both raw and offload-adjusted HBM (see DESIGN.md).
GIANT_ARCHS = {"jamba-1.5-large-398b", "arctic-480b"}

LOSS_CHUNK = 512  # token-chunked CE: never materialize (B,S,V) logits

# Optional config hook applied to the model config in every builder (after
# standard surgery) — the hillclimb harness installs candidate changes here.
EXTRA_CONFIG_HOOK = None


def _apply_hook(model_cfg):
    if EXTRA_CONFIG_HOOK is not None:
        EXTRA_CONFIG_HOOK(model_cfg)


# --------------------------------------------------------------------------
# Config surgery (all config, no code — the paper's modifier mechanism)
# --------------------------------------------------------------------------


def set_param_dtype(model_cfg, dtype):
    def visit(path, cfg):
        if "param_dtype" in cfg.keys():
            cfg.set(param_dtype=dtype)

    visit_config(model_cfg, visit)


def apply_production_mode(model_cfg):
    """bf16 activations (production mixed precision); scans stay rolled."""

    def visit(path, cfg):
        if "activation_dtype" in cfg.keys():
            cfg.set(activation_dtype=jnp.bfloat16)

    visit_config(model_cfg, visit)


def apply_analysis_mode(model_cfg, seq_len: int, depth: int):
    """Cost-analysis variant: XLA tallies a while body ONCE (verified), so we
    (a) shrink the stack to ``depth`` layers/blocks and FULLY unroll it, and
    (b) unroll all inner scans (attention chunks, loss chunks, wkv chunks).
    Lowering depth=1 and depth=2 lets run_one() extrapolate every cost
    (affine in depth: per-layer ops + depth-proportional optimizer update +
    constant embedding/head) to the true L — two tiny compiles instead of one
    giant unrolled one. Pure config; no layer code knows about analysis mode.

    Returns the original depth L."""

    found = []

    def visit(path, cfg):
        if "num_layers" in cfg.keys() and "scan_unroll" in cfg.keys():
            found.append(cfg.num_layers)
            cfg.set(num_layers=depth, scan_unroll=True)
        if "loss_chunk_unroll" in cfg.keys():
            cfg.set(loss_chunk_unroll=True)
        if "activation_dtype" in cfg.keys():
            cfg.set(activation_dtype=jnp.bfloat16)
        if "blockwise_unroll" in cfg.keys():
            cfg.set(blockwise_unroll=True,
                    blockwise_chunk_size=max(seq_len // 8, 512))
        if "wkv_unroll" in cfg.keys():
            cfg.set(wkv_unroll=True, wkv_chunk_size=128)
        if "scan_unroll_chunks" in cfg.keys():
            cfg.set(scan_unroll_chunks=True,
                    scan_chunk_size=max(seq_len // 16, 256))

    visit_config(model_cfg, visit)
    assert len(found) == 1, f"expected exactly one Repeat stack, got {found}"
    return found[0]


def extrapolate_affine(c1: float, c2: float, L: int) -> float:
    """cost(L) for costs affine in depth, from cost(1) and cost(2)."""
    per_layer = c2 - c1
    return max(c1 + (L - 1) * per_layer, 0.0)


_WEIGHT_FIELDS = ("weight_partition", "param_partition_spec")


def _drop_batch_axes(spec):
    if spec is None:
        return None

    def drop(entry):
        if entry in ("pod", "data"):
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a not in ("pod", "data"))
            return kept if len(kept) > 1 else (kept[0] if kept else None)
        return entry

    return tuple(drop(e) for e in spec)


def adapt_for_batch1_decode(model_cfg):
    """long_500k (global_batch=1): batch axes can't shard a size-1 dim.
    Drop pod/data from ACTIVATION partitions (weights keep 2D sharding) and
    move the freed "data" axis onto the KV-cache sequence dim — the
    flash-decoding-style layout (GSPMD inserts the partial-softmax reduce)."""

    def visit(path, cfg):
        for key in cfg.keys():
            if not key.endswith("_partition"):
                continue
            if any(key.endswith(w) for w in _WEIGHT_FIELDS):
                continue
            setattr(cfg, key, _drop_batch_axes(getattr(cfg, key)))
        if "kv_cache_partition" in cfg.keys() and "num_kv_heads" in cfg.keys():
            nh = cfg.num_kv_heads or cfg.num_heads
            hd = cfg.head_dim
            heads_ax = "model" if (nh and nh % MODEL_AXIS == 0) else None
            dim_ax = "model" if heads_ax is None and hd and hd % MODEL_AXIS == 0 else None
            cfg.set(kv_cache_partition=(None, heads_ax, "data", dim_ax))
        if "state_partition" in cfg.keys():
            cfg.set(state_partition=_drop_batch_axes(cfg.state_partition))

    visit_config(model_cfg, visit)


# --------------------------------------------------------------------------
# Step builders
# --------------------------------------------------------------------------


def build_train_lowering(spec, shape: str, mesh, depth: Optional[int] = None):
    info = SHAPES[shape]
    model_cfg = spec.make_model()
    if "loss_chunk_size" in model_cfg.keys():
        model_cfg.set(loss_chunk_size=LOSS_CHUNK)
    giant = spec.arch_id in GIANT_ARCHS
    if giant:
        set_param_dtype(model_cfg, jnp.bfloat16)
    if depth is None:
        apply_production_mode(model_cfg)
    else:
        apply_analysis_mode(model_cfg, info["seq_len"], depth)
    _apply_hook(model_cfg)

    cfg = SpmdTrainer.default_config().set(name="trainer", model=model_cfg)
    cfg.input.set(task={"audio": "audio", "vlm": "vlm"}.get(spec.modality, "lm"),
                  vocab_size=spec.vocab_size, seq_len=info["seq_len"],
                  global_batch_size=info["global_batch"],
                  model_dim=spec.model_dim)
    cfg.learner.optimizer = config_for_function(opt_lib.adamw).set(
        peak_lr=1e-4, weight_decay=0.0,
        moment_dtype=jnp.bfloat16 if giant else jnp.float32)
    trainer = cfg.instantiate()
    trainer._mesh = mesh
    trainer.learner.build(trainer.param_specs())

    state_shapes = jax.eval_shape(trainer.init_state)
    state_sh = trainer.state_shardings(state_shapes, mesh)
    batch_specs = spec.input_specs(shape)
    batch_sh = trainer.batch_shardings(batch_specs, mesh)
    step = trainer.make_train_step()
    lowered = jax.jit(
        step,
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, None),
        donate_argnums=(0,),
    ).lower(state_shapes, batch_specs)

    # Offload-adjusted accounting: bytes that live in host RAM on TPU.
    offload_bytes = 0
    if giant:
        opt_leaves = jax.tree.leaves(state_shapes["opt_state"])
        offload_bytes = sum(
            int(l.size) * l.dtype.itemsize for l in opt_leaves if hasattr(l, "size"))
    return lowered, {"offloadable_bytes_global": offload_bytes}


def _model_and_params(spec, *, seq_len, depth=None):
    model_cfg = spec.make_model()
    set_param_dtype(model_cfg, jnp.bfloat16)  # serving runs bf16 weights
    if depth is None:
        apply_production_mode(model_cfg)
    else:
        apply_analysis_mode(model_cfg, seq_len, depth)
    _apply_hook(model_cfg)
    model = model_cfg.instantiate()
    p_specs = model.create_parameter_specs_recursively()
    param_shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(tuple(s.shape), s.dtype), p_specs,
        is_leaf=lambda s: isinstance(s, ParameterSpec))
    return model_cfg, model, p_specs, param_shapes



def _state_shardings(model, mesh):
    """NamedShardings for the decode-state pytree from the layers' own
    state_partition_specs (config-driven, like everything else)."""
    specs = model.state_partition_specs()

    def rec(node):
        if isinstance(node, dict):
            return {k: rec(v) for k, v in node.items()}
        return named_sharding(node, mesh)

    return rec(specs)


def build_prefill_lowering(spec, shape: str, mesh, depth: Optional[int] = None):
    info = SHAPES[shape]
    B, S = info["global_batch"], info["seq_len"]
    model_cfg, model, p_specs, param_shapes = _model_and_params(
        spec, seq_len=S, depth=depth)
    param_sh = jax.tree.map(
        lambda s: named_sharding(s.mesh_axes, mesh), p_specs,
        is_leaf=lambda s: isinstance(s, ParameterSpec))
    batch_specs = spec.input_specs(shape)
    batch_sh = jax.tree.map(
        lambda x: named_sharding(
            (("pod", "data"),) + (None,) * (len(x.shape) - 1), mesh),
        batch_specs)

    if spec.modality == "audio":
        # Encoder-only: "prefill" is the batched encoder forward.
        def step(params, batch):
            out, _ = functional(model, state=params, inputs=(batch,),
                                method="predict")
            return out

        return jax.jit(step, in_shardings=(param_sh, batch_sh)).lower(
            param_shapes, batch_specs), {}

    cache_shapes = jax.eval_shape(
        lambda: functional(model, state=param_shapes, inputs=(B, S),
                           method="init_states")[0])
    cache_sh = _state_shardings(model, mesh)

    def step(params, cache, batch):
        (cache, logits), _ = functional(
            model, state=params,
            inputs={"state": cache, **{("input_ids" if k == "input_ids" else k): v
                                       for k, v in batch.items()}},
            method="prefill")
        return cache, logits[:, -1]

    lowered = jax.jit(
        step, in_shardings=(param_sh, cache_sh, batch_sh),
        donate_argnums=(1,),
    ).lower(param_shapes, cache_shapes, batch_specs)
    return lowered, {}


def build_decode_lowering(spec, shape: str, mesh, depth: Optional[int] = None):
    info = SHAPES[shape]
    B, S = info["global_batch"], info["seq_len"]
    model_cfg = spec.make_model()
    set_param_dtype(model_cfg, jnp.bfloat16)
    if depth is None:
        apply_production_mode(model_cfg)
    else:
        apply_analysis_mode(model_cfg, S, depth)
    if B == 1:
        adapt_for_batch1_decode(model_cfg)
    _apply_hook(model_cfg)
    model = model_cfg.instantiate()
    p_specs = model.create_parameter_specs_recursively()
    param_shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(tuple(s.shape), s.dtype), p_specs,
        is_leaf=lambda s: isinstance(s, ParameterSpec))
    param_sh = jax.tree.map(
        lambda s: named_sharding(s.mesh_axes, mesh), p_specs,
        is_leaf=lambda s: isinstance(s, ParameterSpec))

    cache_shapes = jax.eval_shape(
        lambda: functional(model, state=param_shapes, inputs=(B, S),
                           method="init_states")[0])
    cache_sh = _state_shardings(model, mesh)
    ids_spec = spec.input_specs(shape)["ids_step"]
    batch_axes = (("pod", "data"),) if B > 1 else (None,)
    ids_sh = named_sharding(batch_axes + (None,), mesh)

    def serve_step(params, cache, ids_step):
        (cache, logits), _ = functional(
            model, state=params, inputs={"state": cache, "ids_step": ids_step},
            method="extend_step")
        return cache, logits[:, -1]

    lowered = jax.jit(
        serve_step, in_shardings=(param_sh, cache_sh, ids_sh),
        donate_argnums=(1,),
    ).lower(param_shapes, cache_shapes, ids_spec)
    return lowered, {}


# --------------------------------------------------------------------------
# Runner
# --------------------------------------------------------------------------


def stack_depth(model_cfg) -> int:
    found = []

    def visit(path, cfg):
        if "num_layers" in cfg.keys() and "scan_unroll" in cfg.keys():
            found.append(cfg.num_layers)

    visit_config(model_cfg, visit)
    assert len(found) == 1, found
    return found[0]


def _build(spec, shape, mesh, depth=None):
    info = SHAPES[shape]
    if info["kind"] == "train":
        return build_train_lowering(spec, shape, mesh, depth)
    if info["kind"] == "prefill":
        return build_prefill_lowering(spec, shape, mesh, depth)
    return build_decode_lowering(spec, shape, mesh, depth)


def run_one(arch: str, shape: str, mesh_kind: str, out_dir: str) -> Dict[str, Any]:
    """Three passes:
      1. PRODUCTION: full depth, rolled scans -> lower+compile (the required
         proof) + memory_analysis (fits-check). Both meshes.
      2+3. ANALYSIS (single-pod only): depth-1 and depth-2 unrolled variants;
         every cost/collective quantity is affine in depth, so cost(L) =
         cost(1) + (L-1)*(cost(2)-cost(1)) — exact without a giant unrolled
         compile (XLA tallies while bodies once; verified empirically).
    """
    spec = registry.get_spec(arch)
    info = SHAPES[shape]
    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    chips = mesh.size
    record: Dict[str, Any] = {
        "arch": arch, "shape": shape, "mesh": mesh_kind, "chips": chips,
        "status": "skip", "family": spec.family,
    }
    if not spec.supports(shape):
        record["skip_reason"] = spec.skip_shapes[shape]
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, f"{arch}__{shape}__{mesh_kind}.json"), "w") as f:
            json.dump(record, f, indent=1)
        return record

    t0 = time.time()
    try:
        with set_mesh(mesh):
            # ---- pass 1: production compile + memory ----------------------
            lowered, extra = _build(spec, shape, mesh, depth=None)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            del lowered, compiled

        L = stack_depth(spec.make_model())
        total, active = registry.param_counts(spec.make_model())
        tokens = info["global_batch"] * (info["seq_len"] if info["kind"] != "decode" else 1)
        mult = 6 if info["kind"] == "train" else 2
        model_flops = mult * active * tokens

        peak_hbm = (mem.argument_size_in_bytes + mem.output_size_in_bytes -
                    mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        record.update(
            status="ok",
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            num_layers=L,
            params_total=total,
            params_active=active,
            model_flops_global=model_flops,
            memory=dict(
                argument_bytes=mem.argument_size_in_bytes,
                output_bytes=mem.output_size_in_bytes,
                alias_bytes=mem.alias_size_in_bytes,
                temp_bytes=mem.temp_size_in_bytes,
                peak_per_device=peak_hbm,
                hbm_limit=int(V5E.hbm_bytes),
                fits=bool(peak_hbm <= V5E.hbm_bytes),
                **extra,
            ),
        )
        if extra.get("offloadable_bytes_global"):
            adj = peak_hbm - extra["offloadable_bytes_global"] / chips
            record["memory"]["peak_per_device_offload_adjusted"] = adj
            record["memory"]["fits_with_offload"] = bool(adj <= V5E.hbm_bytes)

        # ---- passes 2+3: cost analysis via depth extrapolation -------------
        if not multi and not os.environ.get("DRYRUN_SKIP_ANALYSIS"):
            from repro.launch.analysis import parse_collectives_dedup

            costs, colls = [], []
            for depth in (1, 2):
                with set_mesh(mesh):
                    lowered, _ = _build(spec, shape, mesh, depth=depth)
                    comp = lowered.compile()
                    costs.append(comp.cost_analysis())
                    colls.append(parse_collectives_dedup(comp.as_text()))
                    del lowered, comp

            flops = extrapolate_affine(
                float(costs[0].get("flops", 0)), float(costs[1].get("flops", 0)), L)
            bytes_acc = extrapolate_affine(
                float(costs[0].get("bytes accessed", 0)),
                float(costs[1].get("bytes accessed", 0)), L)
            kinds = set(colls[0]) | set(colls[1])
            coll_ex = {}
            for kind in kinds:
                b1 = colls[0].get(kind, {}).get("bytes", 0.0)
                b2 = colls[1].get(kind, {}).get("bytes", 0.0)
                n1 = colls[0].get(kind, {}).get("count", 0)
                n2 = colls[1].get(kind, {}).get("count", 0)
                coll_ex[kind] = {
                    "bytes": extrapolate_affine(b1, b2, L),
                    "count": extrapolate_affine(n1, n2, L),
                }
            coll_bytes = sum(v["bytes"] for v in coll_ex.values())
            compute_s = flops / V5E.peak_flops
            memory_s = bytes_acc / V5E.hbm_bw
            collective_s = coll_bytes / V5E.ici_bw
            terms = {"compute": compute_s, "memory": memory_s,
                     "collective": collective_s}
            total_hlo = flops * chips
            record["roofline"] = dict(
                flops_per_device=flops,
                bytes_per_device=bytes_acc,
                collective_bytes_per_device=coll_bytes,
                collectives=coll_ex,
                compute_s=compute_s,
                memory_s=memory_s,
                collective_s=collective_s,
                dominant=max(terms, key=terms.get),
                model_flops_global=model_flops,
                useful_flops_ratio=(model_flops / total_hlo) if total_hlo else None,
                peak_hbm_bytes=peak_hbm,
            )
    except Exception as e:  # noqa: BLE001 — record and continue
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-2000:])
    finally:
        record["wall_s"] = round(time.time() - t0, 2)

    os.makedirs(out_dir, exist_ok=True)
    variant = getattr(run_one, "variant_name", "")
    suffix = f"__{variant}" if variant else ""
    record["variant"] = variant or "baseline"
    path = os.path.join(out_dir, f"{arch}__{shape}__{mesh_kind}{suffix}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1, default=str)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=registry.ALL_ARCHS + ["all"])
    ap.add_argument("--shape", required=True, choices=registry.SHAPE_NAMES + ["all"])
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    archs = registry.ASSIGNED_ARCHS if args.arch == "all" else [args.arch]
    shapes = registry.SHAPE_NAMES if args.shape == "all" else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                rec = run_one(arch, shape, mesh_kind, args.out)
                status = rec["status"]
                msg = f"[dryrun] {arch:>22} {shape:>12} {mesh_kind:>6}: {status}"
                if status == "ok":
                    m = rec["memory"]
                    msg += (f"  peak={m['peak_per_device']/2**30:.2f}GiB"
                            f" fits={m['fits']}")
                    r = rec.get("roofline")
                    if r:
                        msg += (f" compute={r['compute_s']*1e3:.1f}ms"
                                f" mem={r['memory_s']*1e3:.1f}ms"
                                f" coll={r['collective_s']*1e3:.1f}ms"
                                f" dom={r['dominant']}")
                elif status == "error":
                    msg += f"  {rec['error'][:160]}"
                else:
                    msg += f"  ({rec['skip_reason'][:60]})"
                print(msg, flush=True)


if __name__ == "__main__":
    main()
