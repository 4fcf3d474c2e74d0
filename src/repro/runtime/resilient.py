"""Survivable training loop: streaming data + checkpoints + elasticity +
online straggler rebalancing under one supervisor (docs/DESIGN.md §11).

This is the composition the ROADMAP's "long-run resilience" item asks for.
The pieces existed in isolation — ``data/pipeline.py`` (deterministic
prefetching stream), ``checkpoint/manager.py`` (atomic, rotating, async
shard-aware checkpoints), ``runtime/elastic.py`` (remesh + StragglerMonitor),
``core/load_balance.py`` (per-owner ``speed`` factors), ``reshard_owner_state``
(owner-count migration) — and ``ResilientLoop`` wires them into one loop with
three recovery behaviours:

* **online rebalance** — per-owner step timings feed the ``StragglerMonitor``;
  when a persistent slowdown crosses the threshold the dedication plan is
  re-solved with the *measured* speeds (the paper's measured-cost model
  applied online) and the owner-sharded optimizer state migrates through
  ``reshard_owner_state`` — no restart, no trajectory change.  Hysteresis:
  the speeds baked into the live plan are remembered, and a re-solve fires
  only when the estimate drifts beyond the threshold *relative to them*
  (otherwise a permanently-slow-but-already-rebalanced host would re-fire
  every ``window`` steps forever).
* **owner loss / re-add** — a ``kill`` fault (or, on a real mesh, a device
  loss) shrinks the owner set: the loop remeshes (``remesh``), re-plans at
  the surviving count, migrates momentum + per-variant state, and continues
  the same logical trajectory.  ``readd`` is the inverse.
* **preemption** — the whole job dies and resumes from the latest committed
  checkpoint, which carries the train tree (params + owner-sharded
  ``MuonState`` incl. ``variant_state``), the data-pipeline cursor
  (``Pipeline.state()``) and the owner count at save time — so the resumed
  run replays batch k, k+1, ... exactly and, if the owner count changed in
  between, reshards the restored state onto the live plan.

Invariant (tests/test_resilience.py): the *logical* optimizer trajectory —
params, loss curve, and the unpacked per-matrix rows of momentum and variant
state — is bit-identical to an unfaulted run at equal step counts, for every
registry variant.  This holds because (a) the per-matrix NS math is
independent of which owner slot computes it, (b) ``reshard_owner_state`` is
an exact permutation of logical rows, and (c) the data stream is a pure
function of (seed, step).

In-flight staged accumulators (the accumulation-overlapped bucketed
pipeline) never cross a recovery boundary: faults are handled between steps,
where staged gradient state exists only inside the jit'd step program.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from repro.runtime.elastic import StepTimer, StragglerMonitor, remesh
from repro.runtime.faults import (FaultInjector, FaultPlan, OwnerLost,
                                  Preemption)


@dataclass
class ResilientConfig:
    """Supervisor policy (everything but the optimizer math)."""
    steps: int = 50
    ckpt_every: int = 0             # 0 = no checkpointing
    strategy: str = "greedy"        # dedication strategy for every (re)plan
    accum_steps: int = 1
    # straggler policy
    rebalance: bool = True
    window: int = 8                 # monitor window (steps)
    threshold: float = 1.3          # slowdown ratio that triggers a re-solve
    cooldown: int = 10              # min steps between plan changes
    max_history: int = 1024         # StepTimer bound
    seed: int = 0                   # model init PRNG


@dataclass
class LoopReport:
    """Telemetry of one supervised run (consumed by tests + soak bench)."""
    steps: int = 0                       # logical steps completed
    executed_steps: int = 0              # including replays after preemption
    losses: Dict[int, float] = field(default_factory=dict)   # step -> ema
    step_times: List[float] = field(default_factory=list)
    rebalances: List[dict] = field(default_factory=list)
    recoveries: List[dict] = field(default_factory=list)
    checkpoints: List[int] = field(default_factory=list)
    final_owner_count: int = 0

    def loss_curve(self) -> List[float]:
        """EMA loss by logical step (replayed steps overwrite identically)."""
        return [self.losses[s] for s in sorted(self.losses)]


class ResilientLoop:
    """One supervised production training run (see module docstring).

    Always plans with the default *contiguous* physical layout: plans of
    equal owner count then share pack indices whatever the logical
    assignment, which is what lets a rebalance reuse the compiled step
    (no recompile) and keep bit-identity by construction.
    """

    def __init__(self, model_cfg, data_cfg, *, muon=None, run=None,
                 num_owners: int = 1, mesh=None, ckpt_dir: Optional[str] = None,
                 ckpt_keep: int = 3, faults: Optional[FaultPlan] = None,
                 resume: bool = False, log=None):
        import jax

        from repro.checkpoint.manager import CheckpointManager
        from repro.core.muon import MuonConfig
        from repro.data.pipeline import Pipeline
        from repro.models import model_fns

        self.model_cfg = model_cfg
        self.data_cfg = data_cfg
        self.muon_cfg = muon or MuonConfig()
        self.rcfg = run or ResilientConfig()
        self.mesh = mesh
        self.log = log or (lambda *a, **k: None)
        self.report = LoopReport()

        self.shapes = jax.eval_shape(
            lambda k: model_fns(model_cfg).init(model_cfg, k),
            jax.random.PRNGKey(self.rcfg.seed))
        self._step_cache: dict = {}      # plan signature -> compiled step
        self._install(self._plan_for(num_owners=num_owners))
        self._plan_speed = np.ones(self.num_owners)
        self._last_plan_change = -self.rcfg.cooldown

        self.mgr = (CheckpointManager(ckpt_dir, keep=ckpt_keep)
                    if ckpt_dir else None)
        self.injector = FaultInjector(faults) if faults is not None else None
        self.timer = StepTimer(max_history=self.rcfg.max_history)
        self.monitor = StragglerMonitor(
            num_owners=self.num_owners, window=self.rcfg.window,
            threshold=self.rcfg.threshold)

        from repro.train.step import init_state
        self.state = init_state(model_cfg, self.opt,
                                jax.random.PRNGKey(self.rcfg.seed), mesh=mesh)
        self.pipe = Pipeline(data_cfg, mesh=mesh, start_step=0,
                             sharding=None)
        if resume and self.mgr is not None and self.mgr.latest_step():
            self._restore_from_checkpoint()

    # ------------------------------------------------------------ planning

    def _plan_for(self, num_owners: Optional[int] = None, speed=None):
        from repro.core import api
        if self.mesh is not None:
            return api.dedicate_params(self.shapes, mesh=self.mesh,
                                       strategy=self.rcfg.strategy,
                                       speed=speed)
        return api.dedicate_params(self.shapes, num_owners=num_owners,
                                   strategy=self.rcfg.strategy, speed=speed)

    @staticmethod
    def _plan_signature(plan):
        """Physical-layout key: plans with equal signatures produce the same
        compiled step program (the logical assignment is scheduling
        metadata, not computation)."""
        return tuple(sorted(
            (path, g.key, g.count, g.capacity, plan.num_owners)
            for path, g in plan.groups.items()))

    def _install(self, plan) -> None:
        from repro.core import api
        from repro.train.step import make_train_step
        self.plan = plan
        self.num_owners = plan.num_owners
        self.opt = api.Muon(plan, self.mesh, config=self.muon_cfg)
        sig = self._plan_signature(plan)
        if sig not in self._step_cache:
            # The step donates the old state: the loop keeps no reference
            # to it, and checkpoints copy to host before the next step.
            self._step_cache[sig] = make_train_step(
                self.model_cfg, self.opt, self.mesh,
                accum_steps=self.rcfg.accum_steps, donate=True)
        self.step_fn = self._step_cache[sig]

    # --------------------------------------------------------- checkpoints

    def _checkpoint_tree(self):
        return {"train": self.state._asdict(),
                "data": self.pipe.state(),
                "meta": {"num_owners": np.asarray(self.num_owners,
                                                  np.int64)}}

    def _save_checkpoint(self, step: int, *, block: bool = False) -> None:
        if self.mgr is None:
            return
        self.mgr.save(step, self._checkpoint_tree(), block=block)
        self.report.checkpoints.append(step)

    def _restore_from_checkpoint(self) -> int:
        """Rebuild (state, data cursor) from the latest committed checkpoint;
        reshards the owner-sharded state if the live owner count differs from
        the one at save time.  Returns the resumed step."""
        from repro.core.api import reshard_owner_state
        from repro.train.train_state import TrainState
        like = None
        if self.mesh is not None:
            try:
                like = self._checkpoint_tree()
            except Exception:           # structure drifted; restore replicated
                like = None
        tree = self.mgr.restore(like=like)
        state = TrainState(**tree["train"])
        saved_owners = int(np.asarray(tree["meta"]["num_owners"]))
        if saved_owners != self.num_owners:
            saved_plan = self._plan_for(num_owners=saved_owners)
            opt_state = reshard_owner_state(state.opt_state, saved_plan,
                                            self.plan, self.mesh)
            state = TrainState(state.step, state.params, opt_state,
                               state.loss_ema)
        self.state = state
        self.pipe.restore(tree["data"])
        return int(np.asarray(state.step))

    # ----------------------------------------------------------- recovery

    def _migrate(self, new_plan) -> None:
        """Move the owner-sharded optimizer state onto ``new_plan`` and make
        it the live plan (exact permutation of logical rows)."""
        from repro.core.api import reshard_owner_state
        from repro.train.train_state import TrainState
        opt_state = reshard_owner_state(self.state.opt_state, self.plan,
                                        new_plan, self.mesh)
        self._install(new_plan)
        self.state = TrainState(self.state.step, self.state.params,
                                opt_state, self.state.loss_ema)

    def _rebalance(self, speed: np.ndarray, step: int) -> None:
        """Re-solve the dedication with measured speeds; migrate in place.

        The re-solved plan is adopted only if it lowers the makespan under
        the measured speeds.  A window that straddles the onset of a
        slowdown (or wall-clock noise on a loaded host) can trip the monitor
        at a mild speed gap, where the greedy re-solve is no better than the
        live plan; migrating to it would cost a reshard and gain nothing,
        and the monitor re-fires on the next step with a fuller window."""
        t0 = time.perf_counter()
        old_plan = self.plan
        new_plan = self._plan_for(num_owners=self.num_owners, speed=speed)
        cm = new_plan.cost_model or old_plan.cost_model
        before = after = None
        if cm is not None:
            before = old_plan.assignment.makespan(cm, speed=speed)
            after = new_plan.assignment.makespan(cm, speed=speed)
            if after >= before:
                return
        self._migrate(new_plan)
        latency = time.perf_counter() - t0
        self._plan_speed = np.asarray(speed, float)
        self._last_plan_change = step
        self.monitor.reset()
        self.report.rebalances.append({
            "step": step, "latency_s": latency, "speed": speed.tolist(),
            "makespan_before_s": before, "makespan_after_s": after})
        self.log(f"[rebalance] step {step}: speeds={np.round(speed, 3)} "
                 f"makespan {before} -> {after} ({latency*1e3:.0f} ms)")

    def _resize_owners(self, new_count: int, *, kind: str, step: int,
                       owner: int = -1) -> None:
        """Shared kill/readd path: remesh (if meshed), re-plan, migrate."""
        if new_count < 1:
            raise RuntimeError(
                f"owner loss at step {step} leaves no survivors")
        t0 = time.perf_counter()
        if self.mesh is not None:
            import jax
            live = list(self.mesh.devices.flat)
            if kind == "kill" and 0 <= owner < len(live):
                live = live[:owner] + live[owner + 1:]
            elif kind == "readd":
                live = list(jax.devices())
            self.mesh = remesh(live)
            new_plan = self._plan_for()
        else:
            new_plan = self._plan_for(num_owners=new_count)
        old_count = self.num_owners
        self._migrate(new_plan)
        latency = time.perf_counter() - t0
        if kind == "kill" and self.injector is not None:
            self.injector.on_owner_renumber(owner)
        self.monitor = StragglerMonitor(
            num_owners=self.num_owners, window=self.rcfg.window,
            threshold=self.rcfg.threshold)
        self._plan_speed = np.ones(self.num_owners)
        self._last_plan_change = step
        self.report.recoveries.append({
            "kind": kind, "step": step, "owner": owner,
            "owners": (old_count, self.num_owners), "latency_s": latency})
        self.log(f"[{kind}] step {step}: owners {old_count} -> "
                 f"{self.num_owners} ({latency*1e3:.0f} ms)")

    def _recover_preemption(self, step: int) -> int:
        """The job died; resume from the latest committed checkpoint (or from
        scratch when none committed yet).  Returns the step to resume at."""
        import jax
        t0 = time.perf_counter()
        resumed = 0
        if self.mgr is not None and self.mgr.latest_step() is not None:
            resumed = self._restore_from_checkpoint()
        else:
            from repro.train.step import init_state
            self.state = init_state(self.model_cfg, self.opt,
                                    jax.random.PRNGKey(self.rcfg.seed),
                                    mesh=self.mesh)
            self.pipe.seek(0)
        latency = time.perf_counter() - t0
        self.report.recoveries.append({
            "kind": "preempt", "step": step, "resumed_step": resumed,
            "owners": (self.num_owners, self.num_owners),
            "latency_s": latency})
        self.log(f"[preempt] step {step}: resumed at {resumed} "
                 f"({latency*1e3:.0f} ms)")
        return resumed

    # ---------------------------------------------------------- main loop

    def _owner_times(self, wall_s: float) -> np.ndarray:
        """Per-owner step times as a profiler would export them.  SPMD makes
        every owner's wall clock the step time; injected slow factors model
        the degraded hosts the monitor is there to catch."""
        per_owner = np.full(self.num_owners, wall_s)
        if self.injector is not None:
            per_owner = self.injector.perturb(per_owner)
        return per_owner

    def _maybe_rebalance(self, step: int) -> None:
        if not self.rcfg.rebalance:
            return
        if step - self._last_plan_change < self.rcfg.cooldown:
            return
        if not self.monitor.should_rebalance():
            return
        est = self.monitor.speed_estimate()
        ref = self._plan_speed
        drift = float(np.max(np.maximum(est, ref)
                             / np.maximum(np.minimum(est, ref), 1e-9)))
        if drift <= self.rcfg.threshold:
            return                       # already planned for these speeds
        self._rebalance(est, step)

    def _raise_faults(self, step: int) -> None:
        """Poll the fault script for ``step``.  slow/unslow apply silently
        inside the injector; a control event surfaces as the exception a
        real runtime failure would (device loss, SIGTERM) and the supervisor
        recovers and re-polls, so stacked same-step faults strike one at a
        time against the already-recovered topology."""
        if self.injector is None:
            return
        for ev in self.injector.events_at(step):
            if ev.kind == "kill":
                raise OwnerLost(ev.owner)
            if ev.kind == "preempt":
                raise Preemption()
            if ev.kind == "readd":
                import jax
                with jax.profiler.TraceAnnotation("loop.recover"):
                    self._resize_owners(self.num_owners + 1, kind="readd",
                                        step=step)

    def run(self) -> LoopReport:
        """Run to ``rcfg.steps``.  Each iteration is a profiler step span
        (``train``) holding ``loop.data`` (the next batch), ``loop.step``
        (the step call through ``block_until_ready``), ``loop.report`` (the
        loss read, the monitor, the log line and the rebalance check) and
        ``loop.checkpoint``; owner loss, re-add and preemption run in
        ``loop.recover``.  The spans cost about a microsecond each when no
        profiler runs."""
        import jax
        from jax.profiler import StepTraceAnnotation, TraceAnnotation
        step = int(np.asarray(self.state.step))
        try:
            while step < self.rcfg.steps:
                try:
                    self._raise_faults(step)
                except OwnerLost as e:
                    with TraceAnnotation("loop.recover"):
                        self._resize_owners(self.num_owners - 1, kind="kill",
                                            step=step, owner=e.owner)
                    continue                 # re-poll the same step
                except Preemption:
                    with TraceAnnotation("loop.recover"):
                        step = self._recover_preemption(step)
                    continue

                with StepTraceAnnotation("train", step_num=step):
                    with TraceAnnotation("loop.data"):
                        batch = next(self.pipe)
                    with TraceAnnotation("loop.step"), self.timer:
                        self.state = self.step_fn(self.state, batch)
                        jax.block_until_ready(self.state.loss_ema)
                    self.report.executed_steps += 1
                    with TraceAnnotation("loop.report"):
                        self.report.losses[step] = float(self.state.loss_ema)
                        self.report.step_times.append(self.timer.last)
                        self.monitor.record(self._owner_times(self.timer.last))
                        step += 1
                        if step % 10 == 0:
                            ms = np.mean(self.timer.recent(10)) * 1e3
                            self.log(f"step {step:5d} loss_ema "
                                     f"{float(self.state.loss_ema):.4f} "
                                     f"{ms:.0f} ms/step")
                        self._maybe_rebalance(step)
                    if (self.rcfg.ckpt_every
                            and step % self.rcfg.ckpt_every == 0):
                        with TraceAnnotation("loop.checkpoint"):
                            self._save_checkpoint(step)
        finally:
            self.pipe.close()
            if self.mgr is not None:
                self.mgr.wait()
        self.report.steps = step
        self.report.final_owner_count = self.num_owners
        return self.report
