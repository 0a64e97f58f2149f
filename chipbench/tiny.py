"""Small stand-ins of the cells for the CPU tests: the same programs and
harness, at widths a test run can hold. Not used by a benchmark run."""

import os
import time
import types

from chipbench import harness

ROOT = os.path.dirname(harness.HERE)
harness.program_on_path(ROOT)
TRAIN = "qwen2-1.5b-train-d8.pretrain-4k"
SERVE = "qwen2-1.5b-serve.chat-poisson"
SIZES = {"hidden_size": 128, "num_attention_heads": 4,
         "num_key_value_heads": 2, "intermediate_size": 256,
         "vocab_size": 512, "num_hidden_layers": 2}
OVERRIDES = {
    TRAIN: {"config": dict(SIZES, global_batch=2, instance_type="cpu-local"),
            "mix": {"seq_len": 128}},
    SERVE: {"config": dict(SIZES, serving={
        "page_size": 16, "num_pages": 1 + 4 * 16, "slots": 4, "max_len": 256,
        "prefill_chunk": 32, "prefix_caching": True, "spec_k": 0}),
        "mix": {"rate_per_s": 2.0, "preroll_s": 1, "drain_s": 60,
                "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.8,
                           "min": 8, "max": 160},
                "output": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                           "min": 4, "max": 64}}},
}


def bench():
    """``BENCHMARK.json`` with the entries of the cells still pending."""
    out = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    pending = os.path.join(harness.HERE, "pending")
    for name in sorted(os.listdir(pending)):
        for key, entries in harness.load_json(
                os.path.join(pending, name)).items():
            out[key] = out[key] + entries
    return out


def context(cell, *, seed=2**33 + 5, seconds=1.0, fault=None):
    args = types.SimpleNamespace(workload=cell, seed=seed, seconds=seconds,
                                 trace=0)
    over = dict(OVERRIDES[cell], fault=fault)
    return harness.prepare(ROOT, args, t_start=time.time(), overrides=over,
                           bench=bench())


def run(cell, **kw):
    """One run of the cell's harness on the CPU (the chip check skipped)."""
    import jax

    ctx = context(cell, **kw)
    return harness.run_cell(ctx, jax.devices()[:1])
