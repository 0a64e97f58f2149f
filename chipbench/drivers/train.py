"""Training cells: the compiled ``SpmdTrainer`` step as
``launch.train.build_trainer_config`` builds it under the attached chip's
mesh rule, driven by the benchmark's own rows.

Set-up builds the state from the benchmark's weights, and drives the first
three steps through the same compiled step and feed that the window uses;
their losses, the first gradient (read back from Adam's first moment after
one step) and the parameters' change over the three are kept for the
comparison with the plain reference, which runs after the window once the
program's state is freed. The window then runs whole steps until
``--seconds`` have passed, each step fed, dispatched and its loss fetched.
"""

from __future__ import annotations

import gc
import math
import statistics
import sys
import time

from chipbench import harness, program, traffic

REF_STEPS = 3


def _optimizer_of(tcfg, opt):
    """The optimizer the configuration states, checked against the
    program's own optimizer config."""
    o = tcfg.learner.optimizer
    sched = o.learning_rate
    got = {"b1": o.b1, "b2": o.b2, "eps": o.eps,
           "weight_decay": o.weight_decay, "max_grad_norm": o.max_grad_norm,
           "peak_lr": sched.peak_lr, "warmup_steps": sched.warmup_steps,
           "total_steps": sched.total_steps,
           "end_lr_ratio": sched.end_lr_ratio}
    for k, v in got.items():
        if not math.isclose(float(v), float(opt[k]), rel_tol=1e-12):
            raise SystemExit(f"chipbench: the program's optimizer has {k}={v}"
                             f", the configuration states {opt[k]}")
    return opt


def _adam_mu(opt_state):
    """The first moment of the Adam transform inside the program's chain."""
    for part in opt_state:
        if hasattr(part, "mu") and hasattr(part, "nu"):
            return part.mu
    raise SystemExit("chipbench: no Adam state in the program's optimizer")


def _fault_step(step, fault, rows):
    """A broken timed path, for the harness's own tests."""
    import jax

    if fault == "unchanged_state":
        return jax.jit(lambda s, b: (s, step(s, b)[1]))
    if fault == "half_batch":
        return jax.jit(lambda s, b: step(
            s, {k: v[:rows // 2] for k, v in b.items()}))
    raise ValueError(f"unknown fault {fault!r}")


class Session:
    """The trainer, its mesh and its compiled step, built once; states from
    any seed go through the same programs."""

    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp

        from repro.core.utils import set_mesh
        from repro.launch.train import (attached_instance_type,
                                        build_trainer_config)

        self.ctx = ctx
        cfg, mix = ctx["config"], ctx["mix"]
        self.ref = ref = program.reference(cfg)
        opt = cfg["optimizer"]
        instance = cfg.get("instance_type") or attached_instance_type()
        tcfg = build_trainer_config(
            cfg["arch"], full=True, steps=opt["total_steps"],
            batch=cfg["global_batch"], seq=mix["seq_len"], lr=opt["peak_lr"],
            instance_type=instance)
        program.apply_sizes(tcfg.model, cfg)
        _optimizer_of(tcfg, opt)
        self.trainer = trainer = tcfg.instantiate()
        self.mesh = mesh = trainer.build_mesh()
        if mesh.devices.size != ctx["cell"]["chips"]:
            raise SystemExit(f"chipbench: the mesh {dict(mesh.shape)} does "
                             f"not span the cell's {ctx['cell']['chips']} "
                             "chips")
        self.rows = cfg["global_batch"]
        self.tokens_per_step = self.rows * mix["seq_len"]
        with set_mesh(mesh):
            specs = trainer.param_specs()
            program.check_layout(specs, cfg, ref)
            trainer.learner.build(specs)
            p_sh = trainer.param_shardings(mesh)
            self.weights = jax.jit(
                ref.weights_fn(cfg, jnp.float32),
                out_shardings=ref.from_program_tree(p_sh))
            shapes = jax.eval_shape(lambda k: {
                "step": jnp.zeros((), jnp.int32), "prng_key": k,
                "params": ref.to_program_tree(self.weights(k))},
                jax.random.PRNGKey(0))
            shapes["opt_state"] = jax.eval_shape(trainer.learner.init_state,
                                                 shapes["params"])
            self.shardings = sh = trainer.state_shardings(shapes, mesh)
            self.init_opt = jax.jit(trainer.learner.init_state,
                                    out_shardings=sh["opt_state"])
            first = next(self.batches(0))
            self.batch_sh = trainer.batch_shardings(first, mesh)
            step = jax.jit(trainer.make_train_step(),
                           in_shardings=(sh, self.batch_sh),
                           out_shardings=(sh, None), donate_argnums=(0,))
            if ctx["fault"]:
                step = _fault_step(step, ctx["fault"], self.rows)
            self.step = step
        self.norms = jax.jit(lambda t: leaf_norms(ref.from_program_tree(t)))
        self.change = change_norms_fn(cfg, ref)

    def batches(self, seed):
        cfg = self.ctx["config"]
        return traffic.token_rows(self.ctx["mix"], seed, self.rows,
                                  cfg["vocab_size"])

    def state(self, seed):
        import jax
        import jax.numpy as jnp

        key = self.ref.seed_key(seed)
        params = self.ref.to_program_tree(self.weights(key))
        return jax.device_put({
            "step": jnp.zeros((), jnp.int32), "prng_key": key,
            "params": params, "opt_state": self.init_opt(params)},
            self.shardings)

    def first_steps(self, seed):
        """State, feed and the readings of the first ``REF_STEPS`` steps,
        through the compiled step and feed the window uses."""
        import jax

        from repro.core.utils import set_mesh

        state, batches = self.state(seed), self.batches(seed)
        prog = {"loss": []}
        with set_mesh(self.mesh):
            for i in range(REF_STEPS):
                batch = jax.device_put(next(batches), self.batch_sh)
                state, metrics = self.step(state, batch)
                prog["loss"].append(float(metrics["loss"]))
                if i == 0:
                    prog["grad1"] = {k: float(v) for k, v in self.norms(
                        _adam_mu(state["opt_state"])).items()}
            prog["change"] = {k: float(v) for k, v in self.change(
                self.ref.from_program_tree(state["params"]),
                self.ref.seed_key(seed)).items()}
        jax.block_until_ready(state)
        return state, batches, prog


def leaf_norms(tree_plain):
    import jax.numpy as jnp

    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree_plain.items()}


def change_norms_fn(cfg, ref):
    """(params, key) -> per-leaf norm of params minus the weights the key
    made: the parameters' change since step 0."""
    import jax
    import jax.numpy as jnp

    make = ref.weights_fn(cfg, jnp.float32)

    def change(plain, key):
        w0 = make(key)
        return leaf_norms({k: plain[k] - w0[k] for k in plain})

    return jax.jit(change)


def run(ctx):
    import jax

    from repro.core.utils import set_mesh

    sess = Session(ctx)
    state, batches, prog = sess.first_steps(ctx["seed"])
    step, batch_sh = sess.step, sess.batch_sh
    compiles = harness.CompileCounter()
    ctx["setup_s"] = time.time() - ctx["t_start"]
    with set_mesh(sess.mesh):
        harness.start_trace(ctx)
        compiles.start()
        input_s, losses = [], []
        with harness.annotate(ctx, "bench.window"):
            t0 = time.perf_counter()
            while True:
                with harness.annotate(ctx, "train.input"):
                    ti = time.perf_counter()
                    batch = jax.device_put(next(batches), batch_sh)
                    input_s.append(time.perf_counter() - ti)
                with harness.annotate(ctx, "train.dispatch"):
                    state, metrics = step(state, batch)
                with harness.annotate(ctx, "train.fetch"):
                    losses.append(float(metrics["loss"]))
                if time.perf_counter() - t0 >= ctx["seconds"]:
                    break
            jax.block_until_ready(state)
            t1 = time.perf_counter()
        n_compiles = compiles.stop()
        harness.stop_trace(ctx)
    ctx["window_s"] = t1 - t0
    ctx["memory_peak_bytes"] = harness.memory_peak_bytes(ctx["devices"])
    steps = len(losses)
    ctx["attempted"] = steps
    ctx["failed"] = sum(not math.isfinite(x) for x in losses)
    ctx["records"].update(
        steps=steps, tokens=steps * sess.tokens_per_step, input_s=input_s,
        chips=ctx["cell"]["chips"])
    ctx["annotations"] = ("train.input", "train.dispatch", "train.fetch")
    if n_compiles:
        print(f"chipbench: {n_compiles} compilations inside the window",
              file=sys.stderr)
    ref = sess.ref
    del state, metrics, batch, sess, step
    gc.collect()
    compare(ctx, prog, reference_readings(ctx, ref))


def reference_readings(ctx, ref, *, mm="fp32", rows=None):
    """Losses, first gradient and change of the plain reference over the
    first steps, on the same rows (``rows``: only the first ``rows`` of
    each batch, a fault read in the reference's place). On several chips
    the reference's leaves and rows are spread over all of them."""
    import jax
    import jax.numpy as jnp

    cfg, mix = ctx["config"], ctx["mix"]
    opt = cfg["optimizer"]
    devices = ctx["devices"]
    n = rows or cfg["global_batch"]
    shard = put = None
    if len(devices) > 1:
        shard, put = _spread(ref.leaf_shapes(cfg), devices)
    w = ref.make_weights(cfg, ctx["seed"], jnp.float32, shardings=shard)
    st = ref.adamw_init(w)

    def one(w, st, ids, labels):
        loss, g = jax.value_and_grad(
            lambda w: ref.loss(cfg, w, ids, labels, mm=mm))(w)
        w2, st2 = ref.adamw_step(opt, w, st, g)
        return w2, st2, loss, leaf_norms(g), leaf_norms(st2["mu"])

    one = jax.jit(one, donate_argnums=(0, 1))
    out = {"loss": []}
    batches = traffic.token_rows(mix, ctx["seed"], cfg["global_batch"],
                                 cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        for i in range(REF_STEPS):
            batch = {k: v[:n] for k, v in next(batches).items()}
            if put is not None and n % len(devices) == 0:
                batch = jax.device_put(batch, put)
            w, st, loss, g, mu = one(w, st, batch["input_ids"],
                                     batch["labels"])
            out["loss"].append(float(loss))
            if i == 0:
                out["grad_raw"] = {k: float(v) for k, v in g.items()}
                out["grad1"] = {k: float(v) for k, v in mu.items()}
        out["change"] = {k: float(v) for k, v in change_norms_fn(cfg, ref)(
            w, ref.seed_key(ctx["seed"])).items()}
    del w, st
    gc.collect()
    return out


def _spread(shapes, devices):
    """Plain-keyed shardings that split each leaf's first divisible dim
    over every device, and the sharding of rows over them."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.asarray(devices), ("all",))
    out = {}
    for k, shape in shapes.items():
        spec = [None] * len(shape)
        for i, dim in enumerate(shape):
            if dim % len(devices) == 0:
                spec[i] = "all"
                break
        out[k] = NamedSharding(mesh, PartitionSpec(*spec))
    return out, NamedSharding(mesh, PartitionSpec("all", None))


def gaps(prog, want):
    """The compared numbers (see PERF.md): the worst relative gap of the
    three losses; per leaf, the gap between the two norms of the first
    gradient, and of the change over the three steps, each against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger. Leaves whose reference gradient is under a thousandth of the
    median leaf's move by round-off alone and are left out of the change."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"],
                                                    want["loss"]))

    def worst(got, ref, keys):
        med = statistics.median(ref[k] for k in keys)
        return max(abs(got[k] - ref[k]) / max(ref[k], med) for k in keys)

    return {"loss_rel_gap": loss,
            "grad1_leaf_gap": worst(prog["grad1"], want["grad1"],
                                    list(want["grad1"])),
            "change3_leaf_gap": worst(prog["change"], want["change"],
                                      moving(want))}


def moving(want):
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's; the rest move by round-off alone."""
    med = statistics.median(want["grad_raw"].values())
    return [k for k, v in want["grad_raw"].items() if v >= 1e-3 * med]


def compare(ctx, prog, want):
    for name, value in gaps(prog, want).items():
        harness.check(ctx, name, value)


def calibrate(ctx, seeds, control):
    """Lower readings on every seed; control and fault readings on the
    control seeds (see ``chipbench/calibrate.py``)."""
    import json

    sess = Session(ctx)
    ref = sess.ref
    rows = ctx["config"]["global_batch"]
    for seed in seeds:
        t0 = time.time()
        ctx["seed"] = seed
        state, _, prog = sess.first_steps(seed)
        del state
        gc.collect()
        want = reference_readings(ctx, ref)
        out = {"seed": seed, "program": gaps(prog, want),
               "loss_program": prog["loss"], "loss_reference": want["loss"],
               "worst": worst_leaves(prog, want),
               "left_out": sorted(set(want["grad_raw"]) - set(moving(want)))}
        if seed in control:
            ctrl = reference_readings(ctx, ref, mm="fp8")
            out["control_fp8"] = gaps(ctrl, want)
            out["worst_control"] = worst_leaves(ctrl, want)
            if rows > 1:
                half = reference_readings(ctx, ref, rows=rows // 2)
                out["fault_half_batch"] = gaps(half, want)
            chips = len(ctx["devices"])
            if chips > 1:
                # Without the exchange between chips each steps on its own
                # rows: chip 0's view.
                own = reference_readings(ctx, ref, rows=rows // chips)
                out["fault_no_exchange"] = gaps(own, want)
        out["seconds"] = time.time() - t0
        print(json.dumps(out), flush=True)


def worst_leaves(got, want):
    import statistics

    out = {}
    for key in ("grad1", "change"):
        med = statistics.median(want[key].values())
        gaps = {k: abs(got[key][k] - want[key][k]) / max(want[key][k], med)
                for k in want[key]}
        k = max(gaps, key=gaps.get)
        out[key] = [k, gaps[k], got[key][k], want[key][k]]
    return out
