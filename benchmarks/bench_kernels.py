"""Kernel registry benchmark: per-op per-backend numerical parity vs the ref
oracle, plus dispatch overhead.

Two guarantees tracked across PRs via ``BENCH_kernels.json``:

  * parity — for every op, every backend eligible on this platform (Pallas
    runs interpreted off-TPU) matches the ``ref`` oracle (max abs error);
  * dispatch — a cached ``resolve()`` is <1µs amortized, so the registry
    adds nothing to trace time on the decode/train hot paths (resolution
    never happens inside compiled code at all).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from repro.kernels import registry as reg
from repro.kernels.registry import KernelConfig, KernelFeatures

# Structured results from the last run(); run.py persists this as
# BENCH_kernels.json.
LAST_JSON = None

# Interpret-mode Pallas is slow; keep parity shapes small.
_B, _S, _T, _H, _HKV, _D = 2, 64, 64, 4, 2, 16


def _max_err(a, b):
    return float(np.max(np.abs(np.asarray(a, np.float32)
                               - np.asarray(b, np.float32))))


def _attention_inputs(decode=False):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    Sq = 1 if decode else _S
    q = jax.random.normal(ks[0], (_B, Sq, _H, _D))
    k = jax.random.normal(ks[1], (_B, _T, _HKV, _D))
    v = jax.random.normal(ks[2], (_B, _T, _HKV, _D))
    if decode:
        k_pos = jnp.broadcast_to(jnp.arange(_T), (_B, _T))
        q_pos = jnp.full((_B, 1), _T)
        return q, k, v, q_pos, k_pos
    return q, k, v, None, None


def _parity_cases():
    """(op, backend, fn(kernel_cfg) -> (out, expect)) for every non-ref
    backend of every op; Pallas backends run as pallas:interpret off-TPU."""
    cases = []

    q, k, v, _, _ = _attention_inputs()
    fwd_expect = ref.reference_attention(q, k, v)
    # The backend choice is carried by the KernelConfig that run() builds
    # (op_overrides={op: backend}); the lambda only threads it through.
    for backend in ("blockwise", "pallas"):
        cases.append(("attention.fwd", backend, lambda kc: (
            ops.flash_attention(q, k, v, kernel=kc), fwd_expect)))

    qd, kd, vd, q_pos, k_pos = _attention_inputs(decode=True)
    dec_expect = ref.reference_attention(qd, kd, vd, q_positions=q_pos,
                                         k_positions=k_pos)
    # Decode reads a head-major (B, Hkv, T, D) cache; the oracle above takes
    # token-major K/V.
    kd_cache, vd_cache = jnp.swapaxes(kd, 1, 2), jnp.swapaxes(vd, 1, 2)
    cases.append(("attention.decode", "pallas", lambda kc: (
        ops.decode_attention(qd, kd_cache, vd_cache, q_positions=q_pos,
                             k_positions=k_pos, kernel=kc), dec_expect)))

    x = jax.random.normal(jax.random.PRNGKey(1), (_B, _S, 64))
    scale = jax.random.normal(jax.random.PRNGKey(2), (64,))
    rms_expect = ref.reference_rmsnorm(x, scale)
    cases.append(("rmsnorm", "pallas", lambda kc: (
        ops.rmsnorm(x, scale, kernel=kc), rms_expect)))

    ksplit = jax.random.split(jax.random.PRNGKey(3), 5)
    r = jax.random.normal(ksplit[0], (_B, _S, 2, 8))
    kk = jax.random.normal(ksplit[1], (_B, _S, 2, 8))
    vv = jax.random.normal(ksplit[2], (_B, _S, 2, 8))
    w = jax.random.uniform(ksplit[3], (_B, _S, 2, 8), minval=0.6, maxval=0.99)
    u = jax.random.normal(ksplit[4], (2, 8)) * 0.5
    wkv_expect, _ = ref.reference_wkv6(r, kk, vv, w, u, chunk_size=16)
    cases.append(("wkv6", "pallas", lambda kc: (
        ops.wkv6(r, kk, vv, w, u, kernel=kc)[0], wkv_expect)))
    return cases


def _dispatch_overhead_us(n=20000):
    """Amortized cost of one memoized resolve() on the hot feature set."""
    feats = KernelFeatures(platform=reg.current_platform())
    reg.resolve("attention.decode", feats)  # populate
    t0 = time.perf_counter()
    for _ in range(n):
        reg.resolve("attention.decode", feats)
    return (time.perf_counter() - t0) / n * 1e6


def run():
    global LAST_JSON
    rows = []
    payload = {"parity": {}, "dispatch": {}}
    on_tpu = reg.current_platform() == "tpu"

    for op, backend, fn in _parity_cases():
        # Off-TPU the pallas backends execute through the interpreter —
        # the same block decomposition Mosaic runs, at validation speed.
        kc = KernelConfig().set(
            op_overrides={op: backend}, interpret=(not on_tpu
                                                   and backend == "pallas"),
            blockwise_chunk_size=16, wkv_chunk_size=16)
        t0 = time.perf_counter()
        out, expect = fn(kc)
        out.block_until_ready()
        wall_us = (time.perf_counter() - t0) * 1e6
        err = _max_err(out, expect)
        resolved = kc.backend_for(op)
        rows.append((f"kernels/parity/{op}/{resolved}", wall_us,
                     f"max_abs_err={err:.2e}"))
        payload["parity"].setdefault(op, {})[resolved] = {
            "max_abs_err": err, "ok": bool(err < 5e-4)}

    us = _dispatch_overhead_us()
    rows.append(("kernels/dispatch/cached_resolve", us,
                 f"amortized over 20k resolves; budget 1.0us"))
    stats = reg.dispatch_cache_stats()
    payload["dispatch"] = {
        "cached_resolve_us": us,
        "under_1us": bool(us < 1.0),
        "cache_hits": stats["hits"],
        "cache_entries": stats["size"],
    }
    payload["platform"] = reg.current_platform()
    payload["ops"] = {op: reg.registered_backends(op)
                      for op in reg.registered_ops()}
    LAST_JSON = payload
    return rows
