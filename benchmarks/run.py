"""Benchmark harness: one module per paper table/figure.

  bench_loc        — Table 2 (LoC-complexity of RoPE/MoE integration)
  bench_kernels    — kernel registry: per-op per-backend parity vs ref +
                     memoized dispatch overhead (<1µs budget)
  bench_train      — Table 3 (training step time / roofline bounds)
  bench_checkpoint — §5–§6: save/restore latency, training-thread stall per
                     async save, goodput under injected preemptions
  bench_inference  — Table 4 + Fig 5 (TTFT / TPOT / throughput / cont. batching)
  bench_serving    — serving load: Poisson arrivals through the paged
                     gateway (p50/p99 TTFT/TPOT, tokens/s, preemptions)
  bench_scaling    — Fig 4 (single-pod vs multi-pod scaling from dry-runs)
  bench_observability — metrics/span per-call cost + step-time delta with
                     full observability on vs off (the <1% budget)

Prints ``name,us_per_call,derived`` CSV. Modules may expose a ``LAST_JSON``
dict after ``run()``; it is persisted as ``BENCH_<suffix>.json`` next to the
CWD so the perf trajectory (e.g. decode TPOT) is tracked across PRs. A
module that raises is reported and the harness exits non-zero after the
remaining modules ran.
"""

import json
import sys
import traceback


def main() -> None:
    from repro.core.compile_cache import configure_compile_cache

    configure_compile_cache()
    from benchmarks import (
        bench_checkpoint,
        bench_inference,
        bench_kernels,
        bench_loc,
        bench_observability,
        bench_scaling,
        bench_serving,
        bench_train,
    )

    print("name,us_per_call,derived")
    failed = []
    for mod in (bench_loc, bench_kernels, bench_train, bench_checkpoint,
                bench_inference, bench_serving, bench_scaling,
                bench_observability):
        try:
            rows = mod.run()
        except Exception as e:  # noqa: BLE001 — reported; exit code below
            traceback.print_exc()
            failed.append(mod.__name__)
            rows = [(f"{mod.__name__}/ERROR", -1, str(e)[:80])]
        for name, us, derived in rows:
            print(f"{name},{us:.2f},{derived}")
            sys.stdout.flush()
        payload = getattr(mod, "LAST_JSON", None)
        if payload is not None:
            suffix = mod.__name__.rsplit("bench_", 1)[-1]
            path = f"BENCH_{suffix}.json"
            with open(path, "w") as f:
                json.dump(payload, f, indent=2, sort_keys=True)
            print(f"# wrote {path}", file=sys.stderr)
    if failed:
        sys.exit(f"benchmark modules failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
