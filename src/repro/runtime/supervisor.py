"""Restart supervisor: crash/preemption injection + resume verification (§5).

An in-process harness that runs :class:`SpmdTrainer` the way a cluster
controller would run a job: on a crash it "restarts the process" (a fresh
trainer instance — new jit cache, new iterator, nothing carried over except
the checkpoint directory) and lets the trainer resume from the latest
COMMITTED checkpoint; on a preemption it delivers the signal event and
expects an emergency checkpoint + zero lost steps.

Faults are injected from the trainer's ``step_hook`` so they land at exact
step boundaries:

  ``crash``   — raises :class:`SimulatedCrash` after the step (and, if a
                save was just launched, while that async write is in
                flight); the supervisor then ``abort()``s the checkpointer
                so the half-written step can never commit — the same
                observable outcome as SIGKILL, since shard writes are
                atomic and COMMITTED is written last.
  ``preempt`` — sets the trainer's preemption event; the loop notices at
                the next step boundary, takes a synchronous
                ``emergency_save()`` and raises :class:`Preempted`.

The supervisor attributes the step time lost to each crash (productive work
past the last committed checkpoint, which the restart recomputes) to the
goodput monitor's virtual ``restart_loss`` bucket, and keeps ONE monitor
across attempts so the summary spans the whole supervised run.

``run()`` returns the final trainer result plus ``losses`` — per-step loss
from whichever attempt last executed that step — and
:func:`assert_continuity` checks them against an uninterrupted reference:
with exact state restore and exactly-once data, the curves must match.
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from repro.runtime.goodput import GoodputMonitor, fleet_summary
from repro.runtime.signals import Preempted, SimulatedCrash

__all__ = ["Fault", "Supervisor", "assert_continuity",
           "FleetFault", "FleetSupervisor", "latest_committed_step",
           "step_boundary_skew"]


def step_boundary_skew(rank_events: Dict[Tuple[int, int], List[dict]]
                       ) -> Dict[str, Any]:
    """Straggler gauge from per-rank goodput streams: for every step that
    more than one rank reported, the spread (max − min) across ranks of the
    step's *completion* time (``t_start + dur_s``, ``time.monotonic`` —
    comparable across processes on one host). A persistently large skew
    means one rank finishes its step late every iteration and the others
    burn that time waiting in the gradient collective."""
    by_step: Dict[Tuple[int, int], Dict[int, float]] = {}
    for (attempt, rank), evs in rank_events.items():
        for e in evs:
            if e.get("bucket") != "step" or "step" not in e:
                continue
            if "t_start" not in e or "dur_s" not in e:
                continue
            by_step.setdefault((attempt, e["step"]), {})[rank] = (
                e["t_start"] + e["dur_s"])
    skews: Dict[Tuple[int, int], float] = {
        key: max(by_rank.values()) - min(by_rank.values())
        for key, by_rank in by_step.items() if len(by_rank) > 1}
    if not skews:
        return {"num_steps": 0, "max_skew_s": 0.0, "mean_skew_s": 0.0,
                "max_skew_step": None}
    worst = max(skews, key=skews.get)
    return {
        "num_steps": len(skews),
        "max_skew_s": skews[worst],
        "mean_skew_s": sum(skews.values()) / len(skews),
        "max_skew_step": worst[1],
    }


@dataclasses.dataclass
class Fault:
    """One injected fault: fires once, after ``step`` executes."""

    step: int
    kind: str = "crash"  # "crash" | "preempt"
    fired: bool = False

    def __post_init__(self):
        if self.kind not in ("crash", "preempt"):
            raise ValueError(f"Unknown fault kind {self.kind!r}")


def assert_continuity(losses: Dict[int, float], reference: Dict[int, float],
                      *, atol: float = 0.0):
    """Asserts the supervised run's loss curve matches the reference run's
    (same steps, same losses) — the end-to-end correctness signal for
    checkpoint restore + exactly-once data delivery."""
    if set(losses) != set(reference):
        raise AssertionError(
            f"step sets differ: only-supervised={sorted(set(losses) - set(reference))[:5]} "
            f"only-reference={sorted(set(reference) - set(losses))[:5]}")
    bad = {s: (losses[s], reference[s]) for s in sorted(losses)
           if abs(losses[s] - reference[s]) > atol}
    if bad:
        first = list(bad.items())[:3]
        raise AssertionError(
            f"loss curve diverged at {len(bad)} step(s) (atol={atol}): {first}")


class Supervisor:
    """Runs a trainer config under fault injection with auto-restart."""

    def __init__(self, trainer_cfg, *, max_restarts: int = 8,
                 monitor: Optional[GoodputMonitor] = None):
        self.trainer_cfg = trainer_cfg
        self.max_restarts = max_restarts
        self.monitor = monitor if monitor is not None else GoodputMonitor()

    def run(self, num_steps: Optional[int] = None,
            faults: Sequence[Fault] = ()) -> Dict[str, Any]:
        faults = [dataclasses.replace(f, fired=False) for f in faults]
        losses: Dict[int, float] = {}
        restarts = 0
        attempts: List[Dict[str, Any]] = []
        while True:
            self.monitor.context["attempt"] = restarts
            trainer = self.trainer_cfg.clone().instantiate()
            executed: List[int] = []

            def hook(*, step, state, metrics, trainer=trainer,
                     executed=executed, **_):
                losses[step] = float(metrics["loss"])
                executed.append(step)
                for f in faults:
                    if not f.fired and f.step == step:
                        f.fired = True
                        if f.kind == "crash":
                            raise SimulatedCrash(step)
                        trainer.preemption_event.set()

            try:
                result = trainer.run(num_steps, monitor=self.monitor,
                                     step_hook=hook)
            except SimulatedCrash as e:
                ckpt = getattr(trainer, "checkpointer", None)
                latest = None
                if ckpt is not None:
                    # Process death: the in-flight async write never commits
                    # (abort joins the write thread, so latest_step() below
                    # cannot race a still-live committer).
                    ckpt.abort()
                    latest = ckpt.latest_step()
                lost_steps = [s for s in executed if s >= (latest or 0)]
                lost_s = sum(
                    ev["dur_s"] for ev in self.monitor.events
                    if ev["bucket"] == "step"
                    and ev.get("attempt") == restarts
                    and ev.get("step") in lost_steps)
                self.monitor.add_event("restart_loss", lost_s, virtual=True,
                                       crash_step=e.step,
                                       resumed_from=latest or 0,
                                       lost_steps=len(lost_steps))
                attempts.append({"outcome": "crash", "at_step": e.step,
                                 "resumed_from": latest or 0})
                restarts += 1
                if restarts > self.max_restarts:
                    raise
                continue
            except Preempted as e:
                attempts.append({"outcome": "preempt", "at_step": e.step,
                                 "resumed_from": e.step})
                restarts += 1
                if restarts > self.max_restarts:
                    raise
                continue
            attempts.append({"outcome": "completed"})
            result["losses"] = losses
            result["restarts"] = restarts
            result["attempts"] = attempts
            result["goodput"] = self.monitor.summary()
            return result


# ---------------------------------------------------------------------------
# Fleet supervision: real worker *processes*, elastic world size
# ---------------------------------------------------------------------------


def latest_committed_step(checkpoint_dir: str) -> Optional[int]:
    """The newest ``step_*`` dir containing COMMITTED, or None."""
    latest = None
    if not os.path.isdir(checkpoint_dir):
        return None
    for name in os.listdir(checkpoint_dir):
        if not name.startswith("step_"):
            continue
        if not os.path.exists(os.path.join(checkpoint_dir, name, "COMMITTED")):
            continue
        try:
            step = int(name[len("step_"):])
        except ValueError:
            continue
        latest = step if latest is None else max(latest, step)
    return latest


@dataclasses.dataclass
class FleetFault:
    """One injected fleet fault, fired during attempt ``attempt``.

    ``sigkill``   — SIGKILL rank ``rank`` at the boundary of ``step`` (the
                    worker kills itself in its step hook, so the kill lands
                    at an exact step — and mid-async-save if ``step`` just
                    launched one). Peers block in the next collective until
                    it times out; the supervisor reaps everyone.
    ``sigterm``   — cluster preemption notice: EVERY rank sets its
                    preemption event at ``step`` (an individual-rank SIGTERM
                    would deadlock peers waiting in step collectives while
                    the victim sits in the emergency-save barrier). All
                    ranks emergency-save through the commit barrier and
                    exit 143 with zero lost steps.
    ``save_kill`` — rank ``rank`` dies INSIDE the checkpoint write of the
                    save launched at ``step``, after leaving a torn tmp
                    shard behind: the torn-commit drill. COMMITTED must
                    never appear for that step.
    """

    attempt: int
    step: int
    kind: str = "sigkill"  # "sigkill" | "sigterm" | "save_kill"
    rank: int = 0

    def __post_init__(self):
        if self.kind not in ("sigkill", "sigterm", "save_kill"):
            raise ValueError(f"Unknown fleet fault kind {self.kind!r}")


def _read_jsonl(path: str) -> List[dict]:
    """Reads a worker result stream, tolerating a torn final line (the
    worker may be SIGKILLed mid-write)."""
    records: List[dict] = []
    if not os.path.exists(path):
        return records
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except json.JSONDecodeError:
                pass
    return records


class FleetSupervisor:
    """Supervises an elastic fleet of real worker OS processes.

    Each *attempt* launches ``schedule[min(attempt, len-1)]`` workers
    (``python -m repro.launch.distributed``) against one shared checkpoint
    directory and a fresh per-attempt coordination directory. When the
    attempt dies — a rank SIGKILLed, a torn save, a fleet-wide preemption —
    the next attempt restarts from the latest COMMITTED checkpoint, possibly
    at a *different* world size (that is the elasticity drill: the schedule
    IS the resharding plan).

    ``run()`` merges per-rank result streams into one loss curve (asserting
    every step's loss is identical across the ranks that executed it — the
    SPMD replication invariant), attributes recomputed step time to
    ``restart_loss``, and aggregates per-rank goodput events into a single
    fleet number via :func:`~repro.runtime.goodput.fleet_summary`.
    """

    def __init__(self, workdir: str, *,
                 schedule: Sequence[int] = (1,),
                 steps: int = 12,
                 grad_microbatches: int = 0,
                 builder: str =
                 "repro.launch.distributed:build_tiny_fleet_config",
                 builder_kwargs: Optional[dict] = None,
                 collective_timeout_s: float = 20.0,
                 max_restarts: int = 8,
                 trace: bool = False):
        if not schedule:
            raise ValueError("schedule needs at least one world size")
        self.workdir = workdir
        self.schedule = tuple(schedule)
        self.steps = steps
        self.grad_microbatches = grad_microbatches
        self.builder = builder
        self.builder_kwargs = dict(builder_kwargs or {})
        self.collective_timeout_s = collective_timeout_s
        self.max_restarts = max_restarts
        # trace=True arms per-rank Chrome traces (pid lane = rank) and
        # merges them into <workdir>/trace.json when the fleet completes.
        self.trace = trace
        self.checkpoint_dir = os.path.join(workdir, "ckpt")
        os.makedirs(self.checkpoint_dir, exist_ok=True)

    # ------------------------------------------------------------- internals

    def _spawn(self, attempt: int, world: int,
               fault: Optional[FleetFault]) -> List[subprocess.Popen]:
        from repro.launch.distributed import worker_argv

        import repro

        coord = os.path.join(self.workdir, f"coord{attempt}")
        os.makedirs(coord, exist_ok=True)
        # The file-backed fleet is a CPU substrate: its workers never touch
        # an accelerator, so on a chip host they cannot contend with the
        # process that holds the chip.
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        # repro may be a namespace package (__file__ is None) — resolve the
        # import root from __path__ so workers see the same tree we do.
        src = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
        procs = []
        for rank in range(world):
            kw: Dict[str, Any] = {}
            if fault is not None:
                if fault.kind == "sigterm":
                    kw["sigterm_at_step"] = fault.step
                elif fault.kind == "sigkill" and rank == fault.rank:
                    kw["sigkill_at_step"] = fault.step
                elif fault.kind == "save_kill" and rank == fault.rank:
                    kw["kill_during_save_step"] = fault.step
            argv = worker_argv(
                sys.executable, builder=self.builder,
                builder_kwargs=self.builder_kwargs,
                coordinator_dir=coord, process_index=rank,
                process_count=world,
                grad_microbatches=self.grad_microbatches,
                checkpoint_dir=self.checkpoint_dir,
                result=self._result_path(attempt, rank),
                steps=self.steps,
                collective_timeout_s=self.collective_timeout_s,
                trace=self._trace_path(attempt, rank) if self.trace else "",
                **kw)
            procs.append(subprocess.Popen(
                argv, env=env, stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL))
        return procs

    def _result_path(self, attempt: int, rank: int) -> str:
        return os.path.join(self.workdir, f"a{attempt}_r{rank}.jsonl")

    def _trace_path(self, attempt: int, rank: int) -> str:
        return os.path.join(self.workdir, f"a{attempt}_r{rank}_trace.json")

    def _merge_traces(self, num_attempts: int) -> Optional[str]:
        """Merge every per-rank trace written so far into one fleet trace
        (one pid lane per rank; a rank that died and came back continues on
        the same lane — SIGKILLed attempts may have no file to merge)."""
        from repro.observability.tracing import merge_traces

        paths = []
        for attempt in range(num_attempts):
            world = self.schedule[min(attempt, len(self.schedule) - 1)]
            for rank in range(world):
                p = self._trace_path(attempt, rank)
                if os.path.exists(p):
                    paths.append(p)
        if not paths:
            return None
        out = os.path.join(self.workdir, "trace.json")
        merge_traces(paths, out_path=out)
        return out

    def _babysit(self, procs: List[subprocess.Popen]) -> List[int]:
        """Waits the attempt out. A non-(0|143) exit is a crash: survivors
        are blocked in collectives doomed to time out, so they are reaped
        immediately. A clean/preempted exit starts a grace window for the
        rest (peers may still be draining their own emergency saves)."""
        grace = self.collective_timeout_s + 15.0
        deadline = None
        while True:
            codes = [p.poll() for p in procs]
            if all(c is not None for c in codes):
                break
            if any(c is not None and c not in (0, 143) for c in codes):
                for p in procs:
                    if p.poll() is None:
                        p.kill()
                for p in procs:
                    p.wait()
                break
            if any(c is not None for c in codes):
                if deadline is None:
                    deadline = time.monotonic() + grace
                elif time.monotonic() > deadline:
                    for p in procs:
                        if p.poll() is None:
                            p.kill()
                    for p in procs:
                        p.wait()
                    break
            time.sleep(0.05)
        return [p.returncode for p in procs]

    # ------------------------------------------------------------------- run

    def run(self, faults: Sequence[FleetFault] = ()) -> Dict[str, Any]:
        losses: Dict[int, float] = {}
        attempts: List[Dict[str, Any]] = []
        rank_events: Dict[Tuple[int, int], List[dict]] = {}
        lost_s_total = 0.0
        finals: List[dict] = []
        attempt = 0
        while True:
            world = self.schedule[min(attempt, len(self.schedule) - 1)]
            fault = next((f for f in faults if f.attempt == attempt), None)
            started_from = latest_committed_step(self.checkpoint_dir)
            procs = self._spawn(attempt, world, fault)
            codes = self._babysit(procs)

            attempt_steps: Dict[int, Dict[int, float]] = {}
            preempted = []
            for rank in range(world):
                records = _read_jsonl(self._result_path(attempt, rank))
                rank_events[(attempt, rank)] = [
                    {k: v for k, v in r.items() if k != "kind"}
                    for r in records if r.get("kind") == "event"]
                for r in records:
                    if r.get("kind") == "step":
                        attempt_steps.setdefault(
                            r["step"], {})[rank] = r["loss"]
                    elif r.get("kind") == "preempted":
                        preempted.append(r)
                    elif r.get("kind") == "final":
                        finals.append({"attempt": attempt, "rank": rank, **r})

            # SPMD replication invariant: a step's loss is identical on
            # every rank that reported it (the batch is global-view and the
            # fold is canonical).
            for step, by_rank in attempt_steps.items():
                vals = set(by_rank.values())
                if len(vals) > 1:
                    raise AssertionError(
                        f"attempt {attempt} step {step}: ranks disagree on "
                        f"loss: {by_rank}")
                losses[step] = next(iter(vals))

            crashed = any(c not in (0, 143) for c in codes)
            if crashed:
                committed = latest_committed_step(self.checkpoint_dir)
                resume_at = (committed if committed is not None else -1)
                lost_steps = [s for s in attempt_steps if s >= resume_at + 1]
                lost = sum(
                    e["dur_s"] for (a, _), evs in rank_events.items()
                    if a == attempt for e in evs
                    if e.get("bucket") == "step"
                    and e.get("step") in lost_steps)
                lost_s_total += lost
                attempts.append({
                    "outcome": "crash", "world_size": world,
                    "exit_codes": codes,
                    "resumed_from": committed,
                    "lost_steps": len(lost_steps),
                    "started_from": started_from})
            elif any(c == 143 for c in codes):
                committed = latest_committed_step(self.checkpoint_dir)
                attempts.append({
                    "outcome": "preempt", "world_size": world,
                    "exit_codes": codes,
                    "resumed_from": committed,
                    "preempted": preempted,
                    "started_from": started_from})
            else:
                attempts.append({
                    "outcome": "completed", "world_size": world,
                    "exit_codes": codes, "started_from": started_from})
                goodput = fleet_summary(rank_events, lost_s=lost_s_total)
                input_state = next(
                    (f.get("input_state") for f in finals
                     if f["attempt"] == attempt and f["rank"] == 0), None)
                return {
                    "losses": losses,
                    "restarts": attempt,
                    "attempts": attempts,
                    "goodput": goodput,
                    "input_state": input_state,
                    "finals": finals,
                    "straggler": step_boundary_skew(rank_events),
                    "trace_path": (self._merge_traces(attempt + 1)
                                   if self.trace else None),
                }
            attempt += 1
            if attempt - 1 >= self.max_restarts:
                raise RuntimeError(
                    f"fleet exceeded max_restarts={self.max_restarts}: "
                    f"{attempts}")
