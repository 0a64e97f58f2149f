"""Serving cells: ``ServingGateway.submit``/``step`` over a paged
``InferenceEngine``, driven as an open loop of independent users.

Set-up builds the engine at the configuration's sizes, loads the
benchmark's weights, and warms up every program the traffic uses (each
chunk length of the prefill, the decode step, greedy and sampled first
tokens) with two requests of 2 x chunk - 1 prompt tokens, whose
power-of-two chunks cover every chunk length up to the chunk budget. A
pre-roll of the same traffic, longer than a mean request's lifetime, then
brings the server to its steady state, so that the window opens on as many
running sequences as the rate keeps there. In the window, requests are
submitted when due and the gateway steps whenever it has work; each
request's latency is timed from its due time, from the token timestamps of
the gateway's ``on_token`` callback. After the window, requests already
due are served on (at most ``drain_s``) until each has its first token;
one with no first token by then counts as failed.

The check: a sample of the greedy requests the window finished (pre-roll
ones among them), drawn from the seed and holding the longest of them, is
run through the plain reference over prompt and served tokens; the number
compared is the widest gap by which a served token's logit lies below the
reference's best.
"""

from __future__ import annotations

import gc
import math
import sys
import time

from chipbench import harness, program, traffic

SAMPLE_TOKENS = 400  # served tokens the reference checks, at least


def _fault_hook(scheduler, fault):
    """A broken timed path, for the harness's own tests."""
    if fault != "alter_token":
        raise ValueError(f"unknown fault {fault!r}")
    emit = scheduler._emit
    vocab = scheduler.engine.config.model.decoder.vocab_size

    def altered(seq, tok):
        if len(seq.tokens) == 3:
            tok = (tok + 1) % vocab
        emit(seq, tok)

    scheduler._emit = altered


def build_engine(ctx):
    """The paged engine at the configuration's sizes, served in bf16."""
    import jax.numpy as jnp

    from repro.configs import registry as arch_registry
    from repro.core.config import update_configs_recursively, visit_config
    from repro.inference.engine import InferenceEngine
    from repro.layers.base import DtypePolicy

    cfg = ctx["config"]
    srv = cfg["serving"]
    ref = program.reference(cfg)
    mcfg = program.apply_sizes(arch_registry.get_spec(cfg["arch"]).make_model(),
                               cfg)
    update_configs_recursively(mcfg, {"dtype_policy": DtypePolicy().set(
        param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16)})

    def paged(_, c):
        if getattr(c, "kv_cache_layout", None) == "dense":
            c.set(kv_cache_layout="paged", page_size=srv["page_size"],
                  num_pages=srv["num_pages"])

    visit_config(mcfg, paged)
    engine = InferenceEngine.default_config().set(
        name="engine", model=mcfg, max_len=srv["max_len"],
        slots=srv["slots"]).instantiate()
    program.check_layout(engine.model.create_parameter_specs_recursively(),
                         cfg, ref)
    return engine, ref


def load_weights(ctx, engine, ref, seed):
    import jax.numpy as jnp

    weights = ref.make_weights(ctx["config"], seed, jnp.bfloat16)
    engine.load(ref.to_program_tree(weights))
    return weights


def make_gateway(ctx, engine, ref, seed):
    from repro.serving import ServingGateway

    srv = ctx["config"]["serving"]
    gw = ServingGateway(engine, prefill_chunk=srv["prefill_chunk"],
                        seed=ref.seed32(seed),
                        prefix_caching=srv["prefix_caching"],
                        spec_k=srv["spec_k"])
    if ctx["fault"]:
        _fault_hook(gw.scheduler, ctx["fault"])
    return gw


class OpenLoop:
    """Submits requests at their due times and steps the gateway; keeps
    every token's timestamp (``time.perf_counter``)."""

    def __init__(self, ctx, gw):
        self.ctx, self.gw = ctx, gw
        self.reqs = []

    def submit(self, req, due_abs):
        from repro.serving import SamplingParams

        rec = {"due": due_abs, "prompt": req["prompt"], "times": [],
               "tokens": [], "greedy": req["temperature"] <= 0,
               "max_new_tokens": req["max_new_tokens"]}

        def on_token(_, tok, rec=rec):
            rec["times"].append(time.perf_counter())
            rec["tokens"].append(int(tok))

        with harness.annotate(self.ctx, "serve.submit"):
            rec["rid"] = self.gw.submit(req["prompt"], sampling=SamplingParams(
                max_new_tokens=req["max_new_tokens"],
                temperature=req["temperature"], top_k=req["top_k"]),
                on_token=on_token)
        self.reqs.append(rec)
        return rec

    def drive(self, reqs, t_zero, until, *, stop=None):
        """Runs the loop until ``until`` (perf_counter), or until ``stop()``
        holds or nothing is left to do when ``stop`` is given, submitting
        ``reqs`` (due relative to ``t_zero``) as they fall due; returns the
        requests not yet submitted."""
        pending = sorted(reqs, key=lambda r: r["due"])
        i = 0
        sched = self.gw.scheduler
        while True:
            now = time.perf_counter()
            if now >= until or (stop is not None and stop()):
                break
            while i < len(pending) and t_zero + pending[i]["due"] <= now:
                self.submit(pending[i], t_zero + pending[i]["due"])
                i += 1
            if sched.has_work:
                with harness.annotate(self.ctx, "serve.gateway_step"):
                    self.gw.step()
            elif i < len(pending):
                time.sleep(max(0.0, min(t_zero + pending[i]["due"], until)
                               - time.perf_counter()))
            elif stop is not None:
                break
            else:
                time.sleep(max(0.0, min(until - time.perf_counter(), 0.001)))
        return pending[i:]


def warm_up(gw, vocab, chunk):
    import numpy as np

    from repro.serving import SamplingParams

    rng = np.random.default_rng(0)
    for temp in (0.0, 0.8):
        gw.submit(rng.integers(1, vocab, size=2 * chunk - 1, dtype=np.int32),
                  sampling=SamplingParams(max_new_tokens=3, temperature=temp))
    gw.drain()


def occupancy(sched):
    """Sequences in slots, and the backlog: those waiting or still
    prefilling."""
    live = [s for s in sched._slot_seq if s is not None]
    prefilling = sum(s.prefill_done < len(s.req.prompt) for s in live)
    return {"running": len(live), "backlog": sched.queue_depth + prefilling}


def finished(reqs, t_open, t_close):
    """Requests whose last token came inside the window."""
    return [r for r in reqs if len(r["tokens"]) == r["max_new_tokens"]
            and t_open <= r["times"][-1] < t_close]


def serve_window(ctx, gw, reqs, seconds, *, preroll=(), drain_s=90.0):
    """The pre-roll, the window and the drain (at most ``drain_s``, until
    every request due in the window has its first token); returns the loop, the window's (open, close) and the
    scheduler's counters and occupancy at both ends."""
    loop = OpenLoop(ctx, gw)
    sched = gw.scheduler
    t_zero = time.perf_counter()
    pre_s = max((-r["due"] for r in preroll), default=0.0)
    t_zero += pre_s
    loop.drive(list(preroll), t_zero, t_zero)
    ctx.setdefault("setup_s", time.time() - ctx["t_start"])
    harness.start_trace(ctx)
    compiles = harness.CompileCounter()
    compiles.start()
    stats0 = dict(sched.stats, **occupancy(sched))
    with harness.annotate(ctx, "bench.window"):
        t_open = time.perf_counter()
        rest = loop.drive(reqs, t_open, t_open + seconds)
        t_close = time.perf_counter()
    stats1 = dict(sched.stats, **occupancy(sched))
    n_compiles = compiles.stop()
    harness.stop_trace(ctx)
    for r in rest:  # due inside the window, not reached before it closed
        loop.submit(r, t_open + r["due"])
    window = [r for r in loop.reqs if t_open <= r["due"] < t_close]
    loop.drive([], t_close, t_close + drain_s,
               stop=lambda: all(r["times"] for r in window))
    if n_compiles:
        print(f"chipbench: {n_compiles} compilations inside the window",
              file=sys.stderr)
    return loop, (t_open, t_close), (stats0, stats1), n_compiles


def run(ctx):
    cfg, mix = ctx["config"], ctx["mix"]
    engine, ref = build_engine(ctx)
    weights = load_weights(ctx, engine, ref, ctx["seed"])
    gw = make_gateway(ctx, engine, ref, ctx["seed"])
    tracer = None
    if ctx["tracing"]:
        from repro.observability.tracing import Tracer

        tracer = Tracer(time_fn=time.perf_counter)
        gw.scheduler.tracer = tracer
    warm_up(gw, cfg["vocab_size"], cfg["serving"]["prefill_chunk"])
    reqs, preroll = requests(ctx, ctx["seed"], ctx["seconds"])
    loop, (t_open, t_close), (s0, s1), _ = serve_window(
        ctx, gw, reqs, ctx["seconds"], preroll=preroll,
        drain_s=mix["drain_s"])
    ctx["window_s"] = t_close - t_open
    ctx["memory_peak_bytes"] = harness.memory_peak_bytes(ctx["devices"])
    window_reqs = [r for r in loop.reqs if t_open <= r["due"] < t_close]
    done = finished(loop.reqs, t_open, t_close)
    print(f"chipbench: running {s0['running']} -> {s1['running']}, backlog "
          f"{s0['backlog']} -> {s1['backlog']} (window open -> close); "
          f"{len(done)} requests finished in the window", file=sys.stderr)
    ctx["attempted"] = len(window_reqs)
    ctx["failed"] = sum(not r["times"] for r in window_reqs)
    ctx["records"].update(
        requests=window_reqs, all_requests=loop.reqs, t_open=t_open,
        t_close=t_close, stats0=s0, stats1=s1,
        spans=tracer.events if tracer is not None else [])
    ctx["annotations"] = ("serve.gateway_step", "serve.submit")
    del gw, loop, engine
    gc.collect()
    gap = widest_gap(ctx, ref, weights, sample(ctx, done))
    harness.check(ctx, "served_logit_gap", gap)


def requests(ctx, seed, seconds):
    """The window's requests and the pre-roll's (due before 0)."""
    mix, vocab = ctx["mix"], ctx["config"]["vocab_size"]
    reqs = traffic.open_loop(mix, seed, seconds, vocab)
    pre = traffic.open_loop(mix, seed + 1, mix["preroll_s"], vocab)
    return reqs, [dict(r, due=r["due"] - mix["preroll_s"]) for r in pre]


def sample(ctx, done):
    """Greedy requests of ``done``, drawn from the seed: the longest first,
    then others until ``SAMPLE_TOKENS`` served tokens."""
    greedy = [r for r in done if r["greedy"]]
    if not greedy:
        return []
    greedy.sort(key=lambda r: -(len(r["prompt"]) + len(r["tokens"])))
    rest = greedy[1:]
    order = traffic.rng_for(ctx["seed"], 4).permutation(len(rest))
    out, n = [greedy[0]], len(greedy[0]["tokens"])
    for i in order:
        if n >= SAMPLE_TOKENS:
            break
        out.append(rest[i])
        n += len(rest[i]["tokens"])
    return out


def reference_fn(ctx, ref, *, mm="fp32"):
    """(weights, ids (1, S), at (P,), served (P,)) -> (gap (P,), top (P,)):
    the reference's logits at positions ``at``; ``gap`` is how far the
    served token lies below the best logit, ``top`` the best token."""
    import jax
    import jax.numpy as jnp

    cfg = ctx["config"]

    def fn(w, ids, at, served):
        h = ref.hidden(cfg, w, ids, mm=mm)[0]
        logits = ref.head(w, h[at], mm=mm)
        best = jnp.max(logits, axis=-1)
        mine = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
        return best - mine, jnp.argmax(logits, axis=-1)

    return jax.jit(fn)


def padded(ctx, rec):
    """Prompt + served tokens padded to the configuration's longest
    sequence (one compiled reference for every request), and the positions
    whose logits chose each served token."""
    import numpy as np

    srv = ctx["config"]["serving"]
    seq = ctx["mix"]["prompt"]["max"] + ctx["mix"]["output"]["max"]
    seq = -(-seq // 512) * 512
    n_out = ctx["mix"]["output"]["max"]
    p, t = rec["prompt"], rec["tokens"]
    ids = np.zeros((1, seq), np.int32)
    ids[0, :len(p)] = p
    ids[0, len(p):len(p) + len(t) - 1] = t[:-1]
    at = np.zeros((n_out,), np.int32)
    served = np.zeros((n_out,), np.int32)
    at[:len(t)] = len(p) - 1 + np.arange(len(t))
    served[:len(t)] = t
    if len(p) + len(t) > srv["max_len"]:
        raise ValueError(f"{len(p)} + {len(t)} tokens exceed max_len")
    return ids, at, served, len(t)


def widest_gap(ctx, ref, weights, recs) -> float:
    import jax

    if not recs:
        return math.inf
    fn = reference_fn(ctx, ref)
    worst = 0.0
    with jax.default_matmul_precision("highest"):
        for rec in recs:
            ids, at, served, n = padded(ctx, rec)
            gap, _ = fn(weights, ids, at, served)
            worst = max(worst, float(gap[:n].max()))
    return worst


def calibrate(ctx, seeds, control):
    """Lower readings on every seed at the cell's load; control and an
    altered token on the control seeds (see ``chipbench/calibrate.py``)."""
    import json

    engine, ref = build_engine(ctx)
    warmed = False
    for seed in seeds:
        t0 = time.time()
        ctx["seed"] = seed
        weights = load_weights(ctx, engine, ref, seed)
        gw = make_gateway(ctx, engine, ref, seed)
        if not warmed:
            warm_up(gw, ctx["config"]["vocab_size"],
                    ctx["config"]["serving"]["prefill_chunk"])
            warmed = True
        reqs, preroll = requests(ctx, seed, ctx["seconds"])
        ctx.pop("setup_s", None)
        loop, (t_open, t_close), _, n_comp = serve_window(
            ctx, gw, reqs, ctx["seconds"], preroll=preroll,
            drain_s=ctx["mix"]["drain_s"])
        window = [r for r in loop.reqs if t_open <= r["due"] < t_close]
        done = finished(loop.reqs, t_open, t_close)
        recs = sample(ctx, done)
        del gw, loop
        gc.collect()
        out = {"seed": seed, "requests": len(window), "done": len(done),
               "sampled": len(recs),
               "sampled_tokens": sum(len(r["tokens"]) for r in recs),
               "window_compiles": n_comp,
               "program": {"served_logit_gap": widest_gap(
                   ctx, ref, weights, recs)}}
        if seed in control:
            out.update(control_readings(ctx, ref, weights, recs))
        out["seconds"] = time.time() - t0
        print(json.dumps(out), flush=True)
        del weights
        gc.collect()


def control_readings(ctx, ref, weights, recs):
    """The control: at each served position, the token float8 products put
    first, read in the float32 reference; and a served token altered."""
    import jax
    import numpy as np

    fp32 = reference_fn(ctx, ref)
    fp8 = reference_fn(ctx, ref, mm="fp8")
    ctrl = altered = 0.0
    with jax.default_matmul_precision("highest"):
        for rec in recs:
            ids, at, served, n = padded(ctx, rec)
            _, top8 = fp8(weights, ids, at, served)
            gap, _ = fp32(weights, ids, at, np.asarray(top8))
            ctrl = max(ctrl, float(gap[:n].max()))
            bumped = served.copy()
            bumped[min(3, n - 1)] = (bumped[min(3, n - 1)] + 1) % \
                ctx["config"]["vocab_size"]
            gap, _ = fp32(weights, ids, at, bumped)
            altered = max(altered, float(gap[:n].max()))
    return {"control_fp8": {"served_logit_gap": ctrl},
            "fault_altered_token": {"served_logit_gap": altered}}


def sweep(ctx, rates):
    """One pre-roll and window per offered rate, without drain: whether the
    backlog grows (``chipbench/sweep.py``)."""
    import json

    from chipbench.stats import nearest_rank

    engine, ref = build_engine(ctx)
    load_weights(ctx, engine, ref, ctx["seed"])
    mix, warmed = ctx["mix"], False
    for rate in rates:
        ctx["mix"] = dict(mix, rate_per_s=rate)
        gw = make_gateway(ctx, engine, ref, ctx["seed"])
        if not warmed:
            warm_up(gw, ctx["config"]["vocab_size"],
                    ctx["config"]["serving"]["prefill_chunk"])
            warmed = True
        reqs, preroll = requests(ctx, ctx["seed"], ctx["seconds"])
        ctx.pop("setup_s", None)
        loop, (t_open, t_close), (s0, s1), _ = serve_window(
            ctx, gw, reqs, ctx["seconds"], preroll=preroll, drain_s=0.0)
        window = [r for r in loop.reqs if t_open <= r["due"] < t_close]
        half = t_open + ctx["seconds"] / 2
        ttft = [[(r["times"][0] - r["due"]) * 1e3 if r["times"] and
                 r["times"][0] < t_close else float("inf")
                 for r in window if (r["due"] < half) == first]
                for first in (True, False)]
        gaps = [(b - a) * 1e3 for r in window
                for a, b in zip(r["times"], r["times"][1:]) if b < t_close]
        out = sum(1 for r in loop.reqs for t in r["times"]
                  if t_open <= t < t_close)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(window),
            "offered_tokens_per_s": sum(r["max_new_tokens"] for r in reqs)
            / ctx["seconds"],
            "output_tokens_per_s": out / (t_close - t_open),
            "running_at_open": s0["running"],
            "running_at_close": s1["running"],
            "backlog_at_open": s0["backlog"],
            "backlog_at_close": s1["backlog"],
            "finished": len(finished(loop.reqs, t_open, t_close)),
            "ttft_p50_ms_first_half": nearest_rank(ttft[0], 50),
            "ttft_p50_ms_second_half": nearest_rank(ttft[1], 50),
            "ttft_p90_ms": nearest_rank(ttft[0] + ttft[1], 90),
            "itl_p50_ms": nearest_rank(gaps, 50) if gaps else None,
            "itl_p95_ms": nearest_rank(gaps, 95) if gaps else None,
            "decode_steps": s1["decode_steps"] - s0["decode_steps"],
        }), flush=True)
        del gw, loop
        gc.collect()
