"""FLOP and byte counts against hand counts; latencies from due times."""

import json
import math
import os

import pytest

from chipbench import harness

HERE = os.path.dirname(os.path.abspath(__file__))


def metric(name):
    return harness.load_module(os.path.join(HERE, "metrics", f"{name}.py"))


def config(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


# One qwen2-1.5b layer's projection weights, by hand: q and o 1536 x 1536,
# k and v 1536 x 256, gate, up and down 1536 x 8960.
LAYER = 2 * 1536 * 1536 + 2 * 1536 * 256 + 3 * 1536 * 8960
HEAD = 151936 * 1536


def test_layer_and_head_sizes():
    assert LAYER == 46_792_704
    assert HEAD == 233_373_696
    cfg = config("qwen2-1.5b-train-d8")
    assert metric("train_mfu").matmul_params(cfg) == 8 * LAYER + HEAD


@pytest.mark.parametrize("layers, gflop", [(8, 3.95), (16, 6.50)])
def test_training_flops_per_token(layers, gflop):
    """d8 is the one-chip cell; d16 the four-chip depth (PERF.md)."""
    cfg = dict(config("qwen2-1.5b-train-d8"), num_hidden_layers=layers)
    attn = layers * 3 * 2 * 1536 * 4097  # causal QK^T and PV, fwd + bwd
    want = 6 * (layers * LAYER + HEAD) + attn
    got = metric("train_mfu").flops_per_token(cfg, 4096)
    assert got == want
    assert abs(got / 1e9 - gflop) < 0.01


def test_causal_flash_attention_flops():
    cost = metric("flash_attn_roofline.train").call_cost
    cfg = config("qwen2-1.5b-train-d8")
    pairs = 4096 * 4097 // 2  # (query, key) pairs under the causal mask
    qk = 2 * 128 * pairs * 12 * 2  # 2 FLOP per MAC, 12 heads, 2 rows
    flops, moved = cost(cfg, 2, 4096, "fwd")
    assert flops == 2 * qk  # QK^T and PV
    assert cost(cfg, 2, 4096, "dq")[0] == 3 * qk
    assert cost(cfg, 2, 4096, "dkv")[0] == 4 * qk
    # q and o (12 heads), k and v (2 heads), bf16; lse in f32.
    assert moved == 2 * 2 * 4096 * 12 * 128 * 2 + 2 * 2 * 4096 * 2 * 128 * 2 \
        + 2 * 12 * 4096 * 4


def test_paged_decode_bytes_per_cached_token():
    m = metric("paged_decode_roofline.serve")
    cfg = config("qwen2-1.5b-serve")
    # 16 cached tokens = one page per layer; no query rows.
    per_layer = m.call_bytes(cfg, 16, 16, 0) - 16 * 4  # less the positions
    assert per_layer * cfg["num_hidden_layers"] / 16 == 28_672
    # A 17th token needs a second whole page.
    assert m.call_bytes(cfg, 16, 17, 0) == 2 * m.call_bytes(cfg, 16, 16, 0)
    # One decode row: q in and out, 12 heads of 128, bf16.
    assert m.call_bytes(cfg, 16, 16, 1) - m.call_bytes(cfg, 16, 16, 0) \
        == 2 * 12 * 128 * 2


def test_serve_flops_of_a_chunk_equal_its_tokens():
    m = metric("serve_mfu")
    cfg = config("qwen2-1.5b-serve")
    chunk = m.chunk_flops(cfg, 100, 8, False)
    tokens = sum(m.token_flops(cfg, 100 + i, False) for i in range(8))
    assert math.isclose(chunk, tokens, rel_tol=1e-12)
    assert m.token_flops(cfg, 0, True) - m.token_flops(cfg, 0, False) \
        == 2 * HEAD


def _records(reqs, pre=(), t_open=100.0, t_close=110.0):
    return {"records": {"requests": reqs, "all_requests": list(pre) + reqs,
                        "t_open": t_open, "t_close": t_close},
            "window_s": t_close - t_open}


def test_latencies_are_timed_from_the_due_time():
    """A request served late because an earlier step stalled the loop
    counts its whole wait, not the time from when it was submitted; the
    token gaps and tokens are those inside the window, of every request."""
    reqs = [{"due": 100.0 + i, "times": [100.0 + i + 0.05, 100.0 + i + 0.1]}
            for i in range(9)]
    reqs.append({"due": 109.0, "times": [111.0, 111.2, 111.6]})
    pre = {"due": 95.0, "times": [99.0, 99.5, 100.3, 100.7]}  # pre-roll
    ctx = _records(reqs, [pre])
    ttft = metric("ttft_p50_ms")
    assert ttft.read(ctx) == pytest.approx(50.0)  # 9 of 10 at 50 ms
    for r in reqs[:5]:  # never served: infinite, and counted
        r["times"] = []
    assert sorted(ttft.values(ctx))[-1] == math.inf
    assert ttft.read(ctx) == pytest.approx(2000.0)
    itl = metric("itl_p95_ms")
    assert sorted(itl.values(ctx)) == pytest.approx(
        [50.0] * 4 + [400.0, 800.0])
    assert itl.read(ctx) == pytest.approx(800.0)
    # Tokens emitted inside the window only, pre-roll requests' included.
    out = metric("serve_output_tokens_per_s").read(ctx)
    assert out == pytest.approx(10 / 10.0)
