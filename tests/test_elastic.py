"""Elastic multi-host training drills: world-size-invariant numerics, the
file-backed collective, FSDP parameter sharding, and the fleet supervisor's
kill/reshard/resume scenarios with REAL worker subprocesses.

The fleet tests assert the tentpole acceptance property: a job trained at
world size P, killed at an exact step boundary, and restarted at world size
P' != P from the latest COMMITTED checkpoint produces a loss curve
IDENTICAL to an uninterrupted single-process reference — exact restore +
exactly-once data + canonical gradient fold, end to end across process
boundaries.
"""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro.launch.distributed import DistributedTimeout, FileCollective
from repro.runtime.supervisor import (
    FleetFault,
    FleetSupervisor,
    assert_continuity,
    latest_committed_step,
)
from repro.trainer.train_step import (
    canonical_mean,
    combine_microbatch_grads,
    slice_microbatch,
)

STEPS = 10
CKPT_EVERY = 4
G = 2  # canonical microbatches: every tested world size divides it


def _sup(root, name, schedule, **kw):
    return FleetSupervisor(
        os.path.join(str(root), name), schedule=schedule, steps=STEPS,
        grad_microbatches=G,
        builder_kwargs={"steps": STEPS, "checkpoint_every_n": CKPT_EVERY},
        collective_timeout_s=30.0, **kw)


@pytest.fixture(scope="module")
def fleet_reference(tmp_path_factory):
    """The ground truth: one process, no faults, steps 0..STEPS-1."""
    result = _sup(tmp_path_factory.mktemp("fleet_ref"), "run", (1,)).run()
    assert sorted(result["losses"]) == list(range(STEPS))
    assert result["restarts"] == 0
    return result


# ----------------------------- unit: numerics --------------------------------


def test_slice_microbatch_rows_and_passthrough():
    batch = {"input_ids": np.arange(32).reshape(8, 4),
             "labels": np.arange(32, 64).reshape(8, 4),
             "positions": np.arange(4)}  # non-batch entry: passes through
    mb = slice_microbatch(batch, 1, 4)
    np.testing.assert_array_equal(mb["input_ids"], batch["input_ids"][2:4])
    np.testing.assert_array_equal(mb["labels"], batch["labels"][2:4])
    np.testing.assert_array_equal(mb["positions"], batch["positions"])
    # Microbatches tile the batch exactly.
    rows = np.concatenate([slice_microbatch(batch, m, 4)["input_ids"]
                           for m in range(4)])
    np.testing.assert_array_equal(rows, batch["input_ids"])
    with pytest.raises(ValueError, match="not divisible"):
        slice_microbatch(batch, 0, 3)


def test_combine_microbatch_grads_is_canonical_float32_fold():
    """The fold equals an explicit left-associative float32 accumulation,
    independent of the (bf16-ish) input dtype — the world-size-invariance
    workhorse."""
    rng = np.random.default_rng(0)
    G_ = 4
    per_mb = [[rng.standard_normal((3, 5)).astype(np.float32),
               rng.standard_normal(7).astype(np.float32)] for _ in range(G_)]
    treedef = None
    import jax

    flat, treedef = jax.tree_util.tree_flatten(
        {"a": per_mb[0][0], "b": per_mb[0][1]})
    combined = combine_microbatch_grads(
        [[mb[0], mb[1]] for mb in per_mb], treedef)
    for i, key in enumerate(["a", "b"]):
        acc = np.array(per_mb[0][i], np.float32, copy=True)
        for m in range(1, G_):
            acc += per_mb[m][i]
        acc *= np.float32(1.0 / G_)
        np.testing.assert_array_equal(np.asarray(combined[key]), acc)
    m = canonical_mean([np.float32([2.0, 4.0]), np.float32([4.0, 8.0])])
    np.testing.assert_array_equal(m, np.float32([3.0, 6.0]))


# ------------------------- unit: file collective -----------------------------


def test_file_collective_allgather_and_barrier(tmp_path):
    """Two threads rendezvous through the directory; payloads come back in
    rank order, bitwise, with per-rank key sets."""
    results = [None, None]

    def worker(rank):
        coll = FileCollective(str(tmp_path), process_index=rank,
                              process_count=2, timeout_s=20.0)
        for op in range(3):  # several ops: numbering + cleanup exercised
            payload = {f"r{rank}.op{op}": np.full((2, 2), rank * 10 + op,
                                                  np.float32)}
            results[rank] = coll.allgather(payload)
        coll.barrier()

    threads = [threading.Thread(target=worker, args=(r,)) for r in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    for rank in range(2):
        gathered = results[rank]
        assert len(gathered) == 2
        for src in range(2):
            np.testing.assert_array_equal(
                gathered[src][f"r{src}.op2"],
                np.full((2, 2), src * 10 + 2, np.float32))
    # Steady-state directory size is O(2N) files, not O(ops).
    assert len(os.listdir(tmp_path)) <= 8


def test_file_collective_dead_peer_times_out(tmp_path):
    coll = FileCollective(str(tmp_path), process_index=0, process_count=2,
                          timeout_s=0.2)
    with pytest.raises(DistributedTimeout, match="rank\\(s\\) \\[1\\]"):
        coll.allgather({"x": np.zeros(1)})


# --------------------------- fleet drills (subprocess) -----------------------


@pytest.mark.multiprocess
def test_fleet_two_process_run_matches_single_process(fleet_reference,
                                                      tmp_path):
    """World-size invariance, no faults: a 2-process fleet's loss curve is
    bitwise identical to the single-process reference."""
    result = _sup(tmp_path, "w2", (2,)).run()
    assert result["restarts"] == 0
    assert_continuity(result["losses"], fleet_reference["losses"])
    assert result["input_state"] == fleet_reference["input_state"]


@pytest.mark.multiprocess
def test_fleet_sigkill_reshard_2_to_1(fleet_reference, tmp_path):
    """Rank 1 of a 2-process fleet is SIGKILLed at step 5; the restart runs
    at world size 1 from the step-4 COMMITTED checkpoint and the merged
    curve matches the uninterrupted reference exactly."""
    sup = _sup(tmp_path, "kill21", (2, 1))
    result = sup.run(faults=[FleetFault(attempt=0, step=5, kind="sigkill",
                                        rank=1)])
    first = result["attempts"][0]
    assert first["outcome"] == "crash"
    assert first["world_size"] == 2
    assert first["resumed_from"] == 4
    assert result["attempts"][1]["world_size"] == 1
    assert result["restarts"] == 1
    assert_continuity(result["losses"], fleet_reference["losses"])
    assert result["input_state"] == fleet_reference["input_state"]
    # Fleet goodput aggregated across both attempts' ranks, with the
    # recomputed step time charged as lost.
    g = result["goodput"]
    assert g["num_streams"] == 3  # 2 ranks in attempt 0 + 1 in attempt 1
    assert 0.0 < g["fleet_goodput_fraction"] < 1.0


@pytest.mark.multiprocess
def test_fleet_sigkill_reshard_1_to_2(fleet_reference, tmp_path):
    """The opposite reshard: a single process dies at step 5 and the job
    restarts as a 2-process fleet from the same checkpoint."""
    sup = _sup(tmp_path, "kill12", (1, 2))
    result = sup.run(faults=[FleetFault(attempt=0, step=5, kind="sigkill",
                                        rank=0)])
    assert result["attempts"][0]["resumed_from"] == 4
    assert result["attempts"][1]["world_size"] == 2
    assert_continuity(result["losses"], fleet_reference["losses"])
    assert result["input_state"] == fleet_reference["input_state"]


@pytest.mark.multiprocess
def test_fleet_mid_save_kill_never_commits_torn_step(fleet_reference,
                                                     tmp_path):
    """Rank 1 dies INSIDE the checkpoint write of the step-8 save, leaving a
    torn tmp shard. COMMITTED must never appear for a step with a missing
    shard; the fleet falls back to the previous COMMITTED step (4), and the
    re-save of step 8 (by the restarted 1-process fleet) leaves a step dir
    that is exactly its manifest — no tmp debris, no foreign shards."""
    sup = _sup(tmp_path, "savekill", (2, 1))
    result = sup.run(faults=[FleetFault(attempt=0, step=8, kind="save_kill",
                                        rank=1)])
    first = result["attempts"][0]
    assert first["outcome"] == "crash"
    # The torn step-8 save never became COMMITTED: resume fell back to 4.
    assert first["resumed_from"] == 4
    assert_continuity(result["losses"], fleet_reference["losses"])

    ckpt_dir = sup.checkpoint_dir
    for dirpath, _, files in os.walk(ckpt_dir):
        for fname in files:
            assert ".tmp" not in fname, os.path.join(dirpath, fname)
    # Every COMMITTED step dir holds exactly its index's world-size worth of
    # shards (+aux) — the attempt-0 world-2 debris in step_8 was cleaned by
    # the world-1 re-commit.
    committed = [d for d in sorted(os.listdir(ckpt_dir))
                 if os.path.exists(os.path.join(ckpt_dir, d, "COMMITTED"))]
    assert committed, ckpt_dir
    for d in committed:
        step_dir = os.path.join(ckpt_dir, d)
        with open(os.path.join(step_dir, "index.json")) as f:
            index = json.load(f)
        shards = sorted(f for f in os.listdir(step_dir)
                        if f.startswith("shard_"))
        assert shards == [f"shard_{p}.npz"
                         for p in range(index["process_count"])], (d, shards)
    assert latest_committed_step(ckpt_dir) is not None


@pytest.mark.multiprocess
def test_fleet_sigterm_preempts_all_ranks_with_zero_lost_steps(
        fleet_reference, tmp_path):
    """A cluster preemption notice (SIGTERM drill) reaches every rank at
    step 6: all exit 143 after an emergency save commits through the
    cross-process barrier; the restart loses ZERO steps."""
    sup = _sup(tmp_path, "term", (2,))
    result = sup.run(faults=[FleetFault(attempt=0, step=6, kind="sigterm")])
    first = result["attempts"][0]
    assert first["outcome"] == "preempt"
    assert first["exit_codes"] == [143, 143]
    # The hook set the event after step 6 completed, so the emergency save
    # committed label 7 ("next step to run" — same convention as periodic
    # saves): steps 0..6 are all preserved.
    assert first["resumed_from"] == 7
    assert all(p["committed"] for p in first["preempted"])
    assert_continuity(result["losses"], fleet_reference["losses"])
    # Zero lost steps -> nothing charged to restart_loss.
    assert result["goodput"]["lost_s"] == 0.0


# ------------------------------- FSDP sharding -------------------------------


FSDP_SUBPROCESS = r"""
import jax
import numpy as np

from repro.core.config import config_for_function, update_configs_recursively
from repro.layers import CausalLM, Decoder, Repeat, TransformerLayer
from repro.trainer import optimizers as opt_lib
from repro.trainer.mesh_rules import FsdpModifier
from repro.trainer.trainer import SpmdTrainer

assert len(jax.devices()) == 4

# Baseline = fully replicated params (clear the model's own data-axis
# partitions) so the measured shrink is attributable to FsdpModifier alone.
PART_FIELDS = ["weight_partition", "qkv_weight_partition",
               "out_weight_partition", "up_weight_partition",
               "down_weight_partition", "gate_weight_partition"]


def make(fsdp):
    layer = TransformerLayer.default_config().set(input_dim=32)
    layer.self_attention.set(num_heads=4, num_kv_heads=2)
    layer.feed_forward.set(hidden_dim=64)
    model = CausalLM.default_config().set(
        decoder=Decoder.default_config().set(
            vocab_size=32, dim=32,
            stack=Repeat.default_config().set(
                layer=layer, num_layers=2, remat_policy=None)))
    cfg = SpmdTrainer.default_config().set(
        name="t", model=model, max_steps=2, log_every_n=1, seed=1,
        mesh_shape=(4,), mesh_axis_names=("data",))
    update_configs_recursively(cfg.model, {f: None for f in PART_FIELDS})
    cfg.input.set(task="lm", vocab_size=32, seq_len=16, global_batch_size=8)
    cfg.learner.optimizer = config_for_function(opt_lib.adamw).set(
        peak_lr=1e-2)
    if fsdp:
        cfg = FsdpModifier.default_config().set(
            axes=("data",)).instantiate().apply(cfg)
        assert cfg.fsdp_axes == ("data",)
    return cfg


def per_device_param_bytes(state, shardings):
    total = 0
    for leaf, sh in zip(jax.tree.leaves(state["params"]),
                        jax.tree.leaves(shardings["params"])):
        total += int(np.prod(sh.shard_shape(leaf.shape))) * leaf.dtype.itemsize
    return total


out = {}
for fsdp in (False, True):
    trainer = make(fsdp).instantiate()
    res = trainer.run()
    state = res["state"]
    shardings = trainer.state_shardings(jax.eval_shape(lambda: state))
    for leaf, sh in zip(jax.tree.leaves(state["params"]),
                        jax.tree.leaves(shardings["params"])):
        assert leaf.sharding == sh, (leaf.shape, leaf.sharding, sh)
    out[fsdp] = (per_device_param_bytes(state, shardings),
                 float(res["final"]["loss"]))
ratio = out[False][0] / out[True][0]
assert ratio > 2.0, f"FSDP saved only {ratio:.2f}x on a 4-way data mesh"
assert abs(out[False][1] - out[True][1]) < 1e-4, out
print(f"OK ratio={ratio:.3f}")
"""


def test_fsdp_modifier_shards_params_on_multidevice_mesh():
    """Per-device parameter bytes shrink on a 4-device data mesh under
    FsdpModifier, with losses identical to the replicated run. Subprocess so
    the forced 4-CPU-device topology can't leak into the suite."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = (os.path.abspath(src) + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", FSDP_SUBPROCESS],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OK ratio=" in proc.stdout


def test_fleet_workers_are_pinned_to_cpu(tmp_path, monkeypatch):
    """The file-backed fleet is a CPU substrate: every worker it spawns gets
    JAX_PLATFORMS=cpu, so on a chip host no child contends for the chip
    that the parent process holds."""
    from repro.runtime import supervisor

    spawned = []
    monkeypatch.setattr(supervisor.subprocess, "Popen",
                        lambda argv, env, **kw: spawned.append(env))
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    sup = supervisor.FleetSupervisor(str(tmp_path), schedule=(2,), steps=1)
    sup._spawn(0, 2, None)
    assert [env["JAX_PLATFORMS"] for env in spawned] == ["cpu", "cpu"]
