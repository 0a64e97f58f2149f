"""Training cells sized by their plain reference: ``train.py``'s loop for a
model that ``program.apply_sizes`` does not know. The configuration's
reference module (``configs/<reference>.py``) sets its sizes on the
program's model config (``apply_sizes``) and gives its leaves.

As in ``train.py``: set-up builds the state from the benchmark's weights
and drives the first three steps through the compiled step and feed that
the window uses; the window runs whole steps until ``--seconds`` have
passed; the plain reference runs after the window, and ``train.py``'s
``gaps`` compare the two. The compared loss is the training objective (the
cross entropy plus the model's auxiliary losses), which is what both sides
differentiate. Each step's MoE counters (held assignments, and max over
mean of the held experts' loads, per layer) come back in the same fetch as
its loss, into ``ctx["records"]``.
"""

from __future__ import annotations

import gc
import json
import math
import os
import sys
import time

from chipbench import harness, program, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
base = harness.load_module(os.path.join(HERE, "train.py"))
REF_STEPS = base.REF_STEPS
COUNTERS = ("held_assignments", "held_load_max_over_mean")


def counters(metrics):
    """{counter: per-layer values} from the step's routed summaries."""
    out = {}
    for key, value in metrics["summaries"].items():
        for name in COUNTERS:
            if key.endswith("/" + name):
                out[name] = value
    return out


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
        ref.apply_sizes(tcfg.model, cfg)
        base._optimizer_of(tcfg, opt)
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
                step = base._fault_step(step, ctx["fault"], self.rows)
            self.step = step
        self.norms = jax.jit(
            lambda t: base.leaf_norms(ref.from_program_tree(t)))
        self.change = base.change_norms_fn(cfg, ref)

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
                prog["loss"].append(float(metrics["total_loss"]))
                if i == 0:
                    prog["grad1"] = {k: float(v) for k, v in self.norms(
                        base._adam_mu(state["opt_state"])).items()}
            prog["change"] = {k: float(v) for k, v in self.change(
                self.ref.from_program_tree(state["params"]),
                self.ref.seed_key(seed)).items()}
        jax.block_until_ready(state)
        return state, batches, prog


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
        input_s, losses, held, imbalance = [], [], [], []
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
                    loss, moe = jax.device_get((metrics["loss"],
                                                counters(metrics)))
                    losses.append(float(loss))
                    if moe:
                        held.append([float(x) for x in
                                     moe["held_assignments"]])
                        imbalance.append([float(x) for x in
                                          moe["held_load_max_over_mean"]])
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
        chips=ctx["cell"]["chips"], held_assignments=held,
        held_load_max_over_mean=imbalance)
    ctx["annotations"] = ("train.input", "train.dispatch", "train.fetch")
    if n_compiles:
        print(f"chipbench: {n_compiles} compilations inside the window",
              file=sys.stderr)
    ref = sess.ref
    del state, metrics, batch, sess, step
    gc.collect()
    base.compare(ctx, prog, base.reference_readings(ctx, ref))


def calibrate(ctx, seeds, control):
    """Lower readings on every seed; the float8 control (and, where there
    is more than one row, the half-batch fault) on the control seeds (see
    ``chipbench/calibrate.py``)."""
    sess = Session(ctx)
    ref = sess.ref
    rows = ctx["config"]["global_batch"]
    for seed in seeds:
        t0 = time.time()
        ctx["seed"] = seed
        state, _, prog = sess.first_steps(seed)
        del state
        gc.collect()
        want = base.reference_readings(ctx, ref)
        out = {"seed": seed, "program": base.gaps(prog, want),
               "loss_program": prog["loss"], "loss_reference": want["loss"],
               "worst": base.worst_leaves(prog, want),
               "left_out": sorted(set(want["grad_raw"])
                                  - set(base.moving(want)))}
        if seed in control:
            ctrl = base.reference_readings(ctx, ref, mm="fp8")
            out["control_fp8"] = base.gaps(ctrl, want)
            out["worst_control"] = base.worst_leaves(ctrl, want)
            if rows > 1:
                half = base.reference_readings(ctx, ref, rows=rows // 2)
                out["fault_half_batch"] = base.gaps(half, want)
        out["seconds"] = time.time() - t0
        print(json.dumps(out), flush=True)
