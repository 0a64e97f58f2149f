"""Unified observability: registry semantics, trace schema, trainer/serving
instrumentation, zero-retrace + overhead budgets, fleet trace merge."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.config import config_for_function
from repro.observability import (
    MemorySink,
    build_observability,
    MetricsRegistry,
    ObservabilityConfig,
    ProfilerWindow,
    Tracer,
    compiled_cost,
    device_peak_flops,
    estimate_mfu,
    load_trace,
    merge_traces,
    validate_chrome_trace,
)
from repro.observability.metrics import RECORD_BASE_FIELDS, JsonlSink
from repro.runtime.goodput import GoodputMonitor


# ------------------------------- registry ------------------------------------


def test_registry_instruments_get_or_create():
    reg = MetricsRegistry()
    assert reg.counter("a") is reg.counter("a")
    assert reg.gauge("b") is reg.gauge("b")
    assert reg.histogram("c") is reg.histogram("c")
    reg.counter("a").inc(3)
    reg.gauge("b").set(2.5)
    snap = reg.snapshot()
    assert snap["counters"]["a"]["value"] == 3
    assert snap["gauges"]["b"] == {"value": 2.5, "updates": 1}


def test_histogram_reservoir_bounded_and_representative():
    reg = MetricsRegistry(reservoir_size=64)
    h = reg.histogram("lat")
    for i in range(10_000):
        h.record(float(i))
    snap = h.snapshot()
    # Exact aggregates regardless of sampling; memory stays at the bound.
    assert snap["count"] == 10_000
    assert snap["min"] == 0.0 and snap["max"] == 9999.0
    assert snap["reservoir_len"] == 64
    assert len(h.values) == 64
    # Uniform stream: the sampled median lands near the true median.
    assert 2000.0 < snap["p50"] < 8000.0
    assert snap["p99"] >= snap["p90"] >= snap["p50"]


def test_jsonl_sink_stable_schema(tmp_path):
    path = str(tmp_path / "m.jsonl")
    reg = MetricsRegistry(sinks=[JsonlSink(path)])
    reg.counter("requests").inc()
    reg.gauge("depth").set(4)
    reg.histogram("lat").record(0.01)
    reg.record_event("fault", rank=1, error="boom")
    reg.close()
    records = [json.loads(line) for line in open(path)]
    assert len(records) == 4  # 1 event (immediate) + 3 instruments (flush)
    kinds = {r["kind"] for r in records}
    assert kinds == {"event", "counter", "gauge", "histogram"}
    for r in records:
        for field in RECORD_BASE_FIELDS:
            assert field in r, r
        assert r["schema"] == 1
    ev = next(r for r in records if r["kind"] == "event")
    assert ev["name"] == "fault" and ev["rank"] == 1


def test_goodput_monitor_adopts_registry_schema():
    sink = MemorySink()
    reg = MetricsRegistry(sinks=[sink])
    monitor = GoodputMonitor(sink=reg.goodput_sink())
    with monitor.bucket("step", step=7):
        pass
    monitor.add_event("restart_loss", 1.5, virtual=True)
    names = [r["name"] for r in sink.records]
    assert names == ["goodput/step", "goodput/restart_loss"]
    step_ev = sink.records[0]
    assert step_ev["kind"] == "event" and step_ev["step"] == 7
    assert "dur_s" in step_ev and "t_start" in step_ev


# -------------------------------- tracing ------------------------------------


def test_tracer_emits_valid_chrome_trace(tmp_path):
    tracer = Tracer(pid=3, process_name="rank 3")
    with tracer.span("outer", step=1):
        with tracer.span("inner"):
            pass
    tracer.instant("fault")
    tracer.counter("queue_depth", 5)
    path = tracer.save(str(tmp_path / "t.json"))
    stats = validate_chrome_trace(load_trace(path))
    assert stats["num_spans"] == 2
    assert stats["pids"] == [3]
    names = {e["name"] for e in load_trace(path)["traceEvents"]}
    assert {"outer", "inner", "fault", "queue_depth",
            "process_name"} <= names


def test_validate_rejects_partial_overlap_and_bad_events():
    bad = {"traceEvents": [
        {"name": "a", "ph": "X", "pid": 0, "tid": 0, "ts": 0.0, "dur": 10.0},
        {"name": "b", "ph": "X", "pid": 0, "tid": 0, "ts": 5.0, "dur": 10.0},
    ]}
    with pytest.raises(ValueError, match="partially overlaps"):
        validate_chrome_trace(bad)
    with pytest.raises(ValueError, match="missing required key"):
        validate_chrome_trace({"traceEvents": [{"ph": "X"}]})
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace({})


def test_merge_traces_keeps_per_rank_lanes(tmp_path):
    paths = []
    for rank in range(2):
        t = Tracer(pid=rank, process_name=f"rank {rank}")
        with t.span("step", step=0):
            pass
        paths.append(t.save(str(tmp_path / f"r{rank}.json")))
    out = str(tmp_path / "merged.json")
    merged = merge_traces(paths, out_path=out)
    stats = validate_chrome_trace(merged)
    assert stats["pids"] == [0, 1] and stats["num_spans"] == 2
    # Restart attempts re-emit identical process metadata: merge dedups it.
    remerged = merge_traces([out, paths[0]])
    metas = [e for e in remerged["traceEvents"]
             if e.get("ph") == "M" and e["pid"] == 0]
    assert len(metas) == 1


# ------------------------------- hardware ------------------------------------


V5E = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")


def test_compiled_cost_and_mfu():
    fn = jax.jit(lambda x: (x @ x).sum())
    compiled = fn.lower(jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    cost = compiled_cost(compiled)
    # 64^3 multiply-adds: XLA reports ~2*64^3 flops.
    assert cost["flops"] and cost["flops"] >= 2 * 64**3 * 0.5
    mfu = estimate_mfu(cost["flops"], 1e-3, device=V5E,
                       peak_flops_per_device=1e9)
    assert mfu == pytest.approx(cost["flops"] / 1e-3 / 1e9)
    assert estimate_mfu(None, 1e-3, device=V5E) is None
    assert estimate_mfu(1e6, 0.0, device=V5E) is None
    # The denominator scales with device count.
    assert estimate_mfu(1e6, 1.0, num_devices=2, device=V5E,
                        peak_flops_per_device=1e6) == pytest.approx(0.5)
    # The CPU has no device peak: no MFU, even with an explicit peak.
    assert estimate_mfu(cost["flops"], 1e-3) is None
    assert estimate_mfu(cost["flops"], 1e-3, peak_flops_per_device=1e9) is None


@pytest.mark.parametrize("kind,expect", [
    ("TPU v5 lite", 197e12),
    ("TPU v9 imaginary", ValueError),
    ("cpu", None),
])
def test_device_peak_flops_table(kind, expect):
    """Peaks are keyed by device_kind: a known kind gives its peak, an
    unknown accelerator raises, and the CPU has none (so MFU is None)."""
    device = (jax.devices("cpu")[0] if kind == "cpu" else
              types.SimpleNamespace(platform="tpu", device_kind=kind))
    if expect is ValueError:
        with pytest.raises(ValueError, match="TPU v9 imaginary"):
            device_peak_flops(device)
        with pytest.raises(ValueError, match="TPU v9 imaginary"):
            estimate_mfu(1e12, 1.0, device=device)
        return
    assert device_peak_flops(device) == expect
    mfu = estimate_mfu(197e12, 1.0, device=device)
    assert mfu == (None if expect is None else pytest.approx(1.0))


def test_profiler_window_state_machine(tmp_path):
    w = ProfilerWindow("", start_step=0, stop_step=0)
    assert not w.enabled  # no logdir -> inert
    w.on_step_start(0)
    assert not w.active
    with pytest.raises(ValueError, match="precedes"):
        ProfilerWindow(str(tmp_path), start_step=5, stop_step=3)
    w = ProfilerWindow(str(tmp_path), start_step=1, stop_step=2)
    w.on_step_start(0)
    assert not w.active
    w.on_step_start(1)  # window opens (or records the backend's refusal)
    w.on_step_end(1)
    assert not w.captured or w.error or not w.active
    w.on_step_start(2)
    w.on_step_end(2)
    w.close()
    assert w.captured and not w.active
    # One-shot: a later step never re-opens the window.
    w.on_step_start(3)
    assert not w.active


# --------------------------- trainer integration -----------------------------


def _tiny_trainer_cfg(steps=6, observability=None):
    from repro.layers import CausalLM, Decoder, Repeat, TransformerLayer
    from repro.trainer import optimizers as opt_lib
    from repro.trainer.trainer import SpmdTrainer

    dim = 32
    layer = TransformerLayer.default_config().set(input_dim=dim)
    layer.self_attention.set(num_heads=4, num_kv_heads=2)
    layer.feed_forward.set(hidden_dim=2 * dim)
    model = CausalLM.default_config().set(
        decoder=Decoder.default_config().set(
            vocab_size=32, dim=dim,
            stack=Repeat.default_config().set(layer=layer, num_layers=2,
                                              remat_policy=None)))
    cfg = SpmdTrainer.default_config().set(
        name="t_obs", model=model, max_steps=steps, log_every_n=2,
        observability=observability)
    cfg.input.set(task="lm", vocab_size=32, seq_len=16, global_batch_size=8)
    cfg.learner.optimizer = config_for_function(opt_lib.adamw).set(
        peak_lr=1e-2)
    return cfg


@pytest.fixture(scope="module")
def trainer_run(tmp_path_factory):
    """One instrumented run shared by the trainer-integration tests."""
    tmp = tmp_path_factory.mktemp("obs")
    obs = ObservabilityConfig(metrics_path=str(tmp / "metrics.jsonl"),
                              trace_path=str(tmp / "trace.json"))
    trainer = _tiny_trainer_cfg(steps=6, observability=obs).instantiate()
    result = trainer.run()
    return trainer, result, obs


def test_trainer_trace_has_per_step_spans(trainer_run):
    _, _, obs = trainer_run
    stats = validate_chrome_trace(load_trace(obs.trace_path))
    events = load_trace(obs.trace_path)["traceEvents"]
    step_spans = [e for e in events
                  if e.get("ph") == "X" and e["name"] == "step"]
    # max_steps=6: one compile span (step 0) + five warm step spans.
    assert len(step_spans) == 5
    assert {e["args"]["step"] for e in step_spans} == {1, 2, 3, 4, 5}
    assert any(e["name"] == "compile" for e in events if e.get("ph") == "X")
    assert any(e["name"] == "input_stall" for e in events
               if e.get("ph") == "X")
    assert stats["pids"] == [0]


def test_trainer_summaries_routed_to_registry(trainer_run):
    trainer, result, _ = trainer_run
    snap = result["telemetry"]
    gauges = snap["gauges"]
    # add_summary values (model accuracy/loss) now leave OutputCollection.
    assert gauges["summaries/accuracy"]["value"] is not None
    assert gauges["train/loss"]["value"] == pytest.approx(
        result["final"]["loss"])
    assert gauges["train/grad_norm"]["value"] > 0
    assert gauges["train/param_norm"]["value"] > 0
    assert gauges["train/update_norm"]["value"] > 0
    assert snap["histograms"]["train/step_time_s"]["count"] >= 2
    assert gauges["train/tokens_per_s"]["value"] > 0
    assert gauges["train/tokens_per_s_per_device"]["value"] > 0


def test_trainer_mfu_and_step_cost(trainer_run):
    trainer, result, _ = trainer_run
    cost = result["step_cost"]
    assert cost["flops"] > 0 and cost["peak_hbm_proxy_bytes"] > 0
    gauges = result["telemetry"]["gauges"]
    # The CPU has no device peak: MFU is absent there, never a CPU number.
    assert "hardware/mfu" not in gauges
    assert gauges["hardware/step_flops"]["value"] == cost["flops"]
    # Memoized: the extra lower+compile happens once.
    assert trainer.step_cost_analysis() is trainer.step_cost_analysis()


def test_trainer_metrics_jsonl_valid(trainer_run):
    _, _, obs = trainer_run
    records = [json.loads(line) for line in open(obs.metrics_path)]
    assert records, "metrics sink is empty"
    assert all(r["schema"] == 1 and "kind" in r and "name" in r
               for r in records)
    # Goodput buckets adopted the registry schema (satellite a of the
    # unified stream): step events appear as goodput/step events.
    assert any(r["name"] == "goodput/step" and r["kind"] == "event"
               for r in records)
    assert any(r["name"] == "train/loss" and r["kind"] == "gauge"
               for r in records)


def test_trainer_zero_retrace_with_observability_on(trainer_run):
    trainer, _, _ = trainer_run
    # Instrumentation lives outside jit: the train step compiled exactly
    # once even with metrics + tracing + MFU hooks armed. (The MFU AOT
    # lower+compile is a separate executable, not a _jit_step retrace.)
    assert trainer._jit_step._cache_size() == 1, \
        "observability instrumentation caused a retrace"


def test_trainer_without_observability_unchanged():
    trainer = _tiny_trainer_cfg(steps=2).instantiate()
    result = trainer.run()
    assert result["telemetry"] is None
    assert trainer.observability is None


# --------------------------- serving integration -----------------------------


def _gateway(observability=None, **kw):
    from tests.test_serving import _engine, _tiny_lm

    from repro.serving import ServingGateway

    engine = _engine(_tiny_lm("paged", num_pages=17, page=8), max_len=32,
                     slots=4)
    return ServingGateway(engine, prefill_chunk=8,
                          observability=observability, **kw)


def test_gateway_bounded_telemetry_preserves_percentile_api():
    from repro.serving import SamplingParams

    gw = _gateway(max_done_results=3)
    for i in range(8):
        gw.submit(np.arange(1, 5) % 3 + 1,
                  sampling=SamplingParams(max_new_tokens=4))
    gw.drain()
    # Retention is bounded: completed results and their token queues retire
    # FIFO past the cap — no per-request growth for the process lifetime.
    assert len(gw.scheduler._done) <= 3
    assert len(gw._queues) <= 3
    m = gw.metrics()
    for key in ("queue_depth", "running", "block_utilization", "completed",
                "timeouts", "preemptions", "restores", "prefill_chunks",
                "decode_steps", "max_concurrent", "tokens_out",
                "tokens_per_s", "ttft_p50_s", "ttft_p99_s", "tpot_p50_s",
                "tpot_p99_s"):
        assert key in m, key
    assert m["completed"] == 8  # counters survive result retirement
    assert m["ttft_p50_s"] > 0 and m["tpot_p50_s"] > 0
    assert m["ttft_p99_s"] >= m["ttft_p50_s"]
    # ...and the reservoirs saw every completed request.
    assert gw.registry.histogram("serving/ttft_s").count == 8


def test_gateway_request_lifecycle_spans(tmp_path):
    obs_cfg = ObservabilityConfig(trace_path=str(tmp_path / "serve.json"))
    obs = build_observability(obs_cfg)
    gw = _gateway(observability=obs)
    rids = [gw.submit(np.arange(1, 6), priority=p) for p in (0, 1)]
    gw.drain()
    obs.save_trace()
    trace = load_trace(obs_cfg.trace_path)
    validate_chrome_trace(trace)
    spans = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)
    # Lifecycle spans per request on the request's own tid lane...
    for name in ("queued", "prefill", "decode"):
        assert {e["tid"] for e in by_name[name]} == set(rids), name
    # ...plus live chunk/decode spans and queue counter samples.
    assert by_name["prefill_chunk"] and by_name["decode_step"]
    assert any(e["name"] == "queue_depth" and e["ph"] == "C"
               for e in trace["traceEvents"])
    # Per-step gauges landed in the shared registry.
    snap = obs.registry.snapshot()
    assert "serving/queue_depth" in snap["gauges"]
    assert "serving/page_pool_utilization" in snap["gauges"]


def test_serving_instrumentation_zero_retrace():
    obs = build_observability(ObservabilityConfig(trace_path="unused.json"))
    gw = _gateway(observability=obs)
    engine = gw.scheduler.engine
    for _ in range(2):
        gw.submit(np.arange(1, 6))
    gw.drain()
    compiles = {k: fn._cache_size() for k, fn in engine._jit_fns.items()}
    for _ in range(3):
        gw.submit(np.arange(1, 6))
    gw.drain()
    after = {k: fn._cache_size() for k, fn in engine._jit_fns.items()}
    assert after == compiles, "instrumented serving loop retraced"
    assert all(v == 1 for v in after.values())


# ------------------------------ fleet merge ----------------------------------


def test_fleet_two_process_merged_trace(tmp_path):
    """2-rank fleet -> ONE merged Chrome trace: per-rank pid lanes,
    per-step spans on each, valid against the trace-event schema, plus the
    step-boundary straggler gauge (the issue's acceptance gate)."""
    from repro.runtime.supervisor import FleetSupervisor

    sup = FleetSupervisor(
        str(tmp_path), schedule=(2,), steps=4, grad_microbatches=2,
        trace=True, builder_kwargs={"steps": 4, "checkpoint_every_n": 4})
    res = sup.run()
    assert res["trace_path"] and os.path.exists(res["trace_path"])
    trace = load_trace(res["trace_path"])
    stats = validate_chrome_trace(trace)
    assert stats["pids"] == [0, 1]
    for rank in (0, 1):
        steps = {e["args"]["step"] for e in trace["traceEvents"]
                 if e.get("ph") == "X" and e["name"] in ("step", "compile")
                 and e["pid"] == rank}
        assert steps == {0, 1, 2, 3}, f"rank {rank} missing step spans"
    skew = res["straggler"]
    assert skew["num_steps"] > 0
    assert skew["max_skew_s"] >= skew["mean_skew_s"] >= 0


def test_step_boundary_skew_math():
    from repro.runtime.supervisor import step_boundary_skew

    events = {
        (0, 0): [{"bucket": "step", "step": 1, "t_start": 10.0, "dur_s": 1.0},
                 {"bucket": "step", "step": 2, "t_start": 12.0, "dur_s": 1.0}],
        (0, 1): [{"bucket": "step", "step": 1, "t_start": 10.0, "dur_s": 1.5},
                 {"bucket": "init", "t_start": 0.0, "dur_s": 5.0}],
    }
    skew = step_boundary_skew(events)
    assert skew["num_steps"] == 1  # step 2 seen by one rank only
    assert skew["max_skew_s"] == pytest.approx(0.5)
    assert skew["max_skew_step"] == 1
    assert step_boundary_skew({})["num_steps"] == 0


# ------------------------------ overhead gate --------------------------------


def test_observability_overhead_under_budget(tmp_path):
    """Per-log-step instrumentation cost stays under an absolute 1ms —
    <1% of any real (100ms+) training step even at log_every_n=1.

    Asserted as an absolute bound on the full metrics-export path (all
    per-step gauges + histogram + MFU + delta flush into a real JSONL
    sink), measured in place during an instrumented run, NOT as an
    off-vs-on step-time A/B: on a sub-3ms toy CPU step under CI load the
    A/B delta is dominated by scheduler/GC noise and flakes either way
    (``bench_observability`` reports the exact interleaved-median delta,
    for an idle machine). A companion bound pins the tracer's per-span
    cost, so both halves of the hot path are enforced."""
    import statistics
    import time

    obs = ObservabilityConfig(metrics_path=str(tmp_path / "m.jsonl"),
                              trace_path=str(tmp_path / "t.json"))
    trainer = _tiny_trainer_cfg(steps=16, observability=obs).set(
        log_every_n=1).instantiate()
    costs = []
    orig = trainer._export_step_metrics

    def timed(*args, **kwargs):
        t0 = time.perf_counter()
        r = orig(*args, **kwargs)
        costs.append(time.perf_counter() - t0)
        return r

    trainer._export_step_metrics = timed
    trainer.run()
    assert len(costs) >= 15  # every step logged
    export_cost = statistics.median(costs)
    assert export_cost < 1e-3, (
        f"per-log-step metrics export {export_cost * 1e6:.0f}us exceeds "
        f"the 1ms budget (<1% of a real 100ms step)")

    tracer = trainer.observability.tracer
    t0 = time.perf_counter()
    for _ in range(1000):
        with tracer.span("budget_probe"):
            pass
    per_span = (time.perf_counter() - t0) / 1000
    assert per_span < 50e-6, (
        f"tracer span cost {per_span * 1e6:.1f}us exceeds 50us budget")
