"""Serve-side checkpoint-restart: durable continuous batching.

Training failover (``distributed/failover.py``) is policy over *hosts*; this
module applies the same checkpoint-restart shape to the *serving* loop, where
the unit of loss is an in-flight request mid-stream.

* :class:`DurableBatcher` — a ``RequestBatcher`` that snapshots the complete
  scheduler state through ``distributed.checkpoint`` at step boundaries: the
  engine cache + threaded PRNG key + per-slot tok/pos/active as the array
  tree, and the host-side request/queue/slot/budget bookkeeping (plus the
  active fault plan and fault-step counter) as the JSON ``extra``.  The step
  boundary — after retire, before the next admission wave — is the loop's
  consistency point: ``_drive`` re-entered from a restored ``_RunState``
  replays the exact admission order, key splits, and fault keys of the
  uninterrupted run, so every request's tokens come out bit-identical.

* :class:`ServeSupervisor` — wires ``HeartbeatMonitor`` + ``FailoverPolicy``
  around the drive loop.  The batcher heartbeats every decode step; a crash
  (any exception escaping the loop — tests raise :class:`SimulatedCrash`
  from the step hook) silences the heartbeat, the policy rules the host
  ELASTIC_DOWN, and the supervisor starts a fresh process surrogate (a new
  batcher from the factory, i.e. new engine state) that ``resume()``s from
  the last complete snapshot and finishes every in-flight request.
"""
from __future__ import annotations

import logging
from typing import Any, Callable

import jax
import numpy as np

from repro.distributed import checkpoint
from repro.distributed.failover import (Action, FailoverPolicy,
                                        HeartbeatMonitor, StragglerDetector)
from repro.reliability import guards
from repro.reliability.faults import FaultPlan
from repro.serving.engine import (_FRESH_STATS, GenerationConfig, Request,
                                  RequestBatcher, ServeEngine, _RunState,
                                  _Slot)

log = logging.getLogger("repro.serving")


class SimulatedCrash(RuntimeError):
    """Raised from a step hook to model a process kill mid-drain (tests)."""


class DurableBatcher(RequestBatcher):
    """A ``RequestBatcher`` whose scheduler loop survives process death.

    ``snapshot_every``: snapshot cadence in decode steps (every boundary is a
    valid point; snapshotting is the cost knob).  ``on_step(step)`` runs at
    every step boundary *before* the snapshot — the supervisor heartbeats
    here, and tests inject crashes here (so a crash step is never persisted,
    like a real kill).
    """

    def __init__(self, engine: ServeEngine, prompt_buckets=(128, 512, 2048),
                 max_queue: int | None = None, *, ckpt_dir: str,
                 snapshot_every: int = 4, keep: int = 3,
                 on_step: Callable[[int], None] | None = None, **kw):
        super().__init__(engine, prompt_buckets, max_queue, **kw)
        self.ckpt_dir = ckpt_dir
        self.snapshot_every = max(1, snapshot_every)
        self.keep = keep
        self.on_step = on_step

    # -- snapshot ---------------------------------------------------------

    def _on_step_boundary(self, st: _RunState):
        if self.on_step is not None:
            self.on_step(st.step)
        if st.step % self.snapshot_every == 0:
            self.snapshot(st)

    def _array_tree(self, st: _RunState) -> dict:
        return {"cache": self.engine.cache, "key": st.key,
                "tok": st.tok, "pos": st.pos, "active": st.active,
                "level": st.level}

    def snapshot(self, st: _RunState) -> str:
        """Persist the complete drain state; returns the checkpoint dir."""
        eng = self.engine
        seen: dict[int, Request] = {}
        for slot in st.slots:
            if slot is not None:
                seen[slot.req.rid] = slot.req
        for r in self.queue:
            seen[r.rid] = r
        extra = {
            "step": st.step,
            "gen": {"max_new_tokens": st.gen.max_new_tokens,
                    "temperature": st.gen.temperature,
                    "top_k": st.gen.top_k, "eos_id": st.gen.eos_id,
                    "pad_id": st.gen.pad_id},
            "cap_budget": st.cap_budget,
            "slots": [None if s is None else
                      {"rid": s.req.rid, "budget": s.budget, "seq": s.seq}
                      for s in st.slots],
            "admit_seq": self._admit_seq,
            # paged engines: the pool bytes ride in the array tree (they ARE
            # eng.cache); this records the page tables that address them
            "paged": None if eng.kv is None else eng.kv.snapshot(),
            "requests": [{"rid": r.rid, "prompt": [int(t) for t in r.prompt],
                          "max_new": r.max_new, "out": [int(t) for t in r.out],
                          "done": r.done, "deadline_ms": r.deadline_ms,
                          "submit_t": r.submit_t, "level": r.level,
                          "attempts": r.attempts, "status": r.status}
                         for r in seen.values()],
            "queue": [r.rid for r in self.queue],
            "next_rid": self._next_rid,
            "results": {str(k): [int(t) for t in v]
                        for k, v in st.results.items()},
            "events": [list(e) for e in self.events],
            "stats": dict(self.stats),
            "statuses": {str(k): v for k, v in self.statuses.items()},
            "fault": None if eng.fault is None else eng.fault.to_dict(),
            "fault_step": eng.fault_step,
            "guards": guards.snapshot(),
        }
        return checkpoint.save(self.ckpt_dir, st.step, self._array_tree(st),
                               keep=self.keep, extra=extra)

    # -- restore ----------------------------------------------------------

    def resume(self, *, step: int | None = None, on_complete=None,
               max_steps: int | None = None):
        """Restore the last (or given) snapshot and drain to completion.

        Call on a freshly-built batcher (new engine = the restarted process);
        pre-existing queue/engine state is overwritten by the snapshot.
        Returns the full {rid: tokens} results dict, including requests that
        had already completed before the snapshot."""
        eng = self.engine
        B = eng.batch
        # layout check BEFORE array restore: a dense/paged mismatch must
        # surface as this error, not as a leaf shape mismatch deep in
        # checkpoint.restore
        extra_peek, step = checkpoint.read_extra(self.ckpt_dir, step)
        snap_paged = extra_peek.get("paged")
        if (snap_paged is None) != (eng.kv is None):
            raise RuntimeError(
                "snapshot/engine cache layout mismatch: "
                f"snapshot is {'paged' if snap_paged else 'dense'}, engine "
                f"is {'paged' if eng.kv is not None else 'dense'}")
        target = {"cache": eng.cache, "key": jax.random.PRNGKey(0),
                  "tok": np.zeros(B, np.int32), "pos": np.zeros(B, np.int64),
                  "active": np.zeros(B, bool),
                  "level": np.zeros(B, np.int32)}
        tree, ck_step, extra = checkpoint.restore(self.ckpt_dir, target,
                                                  step=step)
        eng.cache = tree["cache"]
        if eng.kv is not None:
            eng.kv.load(snap_paged)
        self._admit_seq = extra.get("admit_seq", 0)
        eng.fault = (None if extra["fault"] is None
                     else FaultPlan.from_dict(extra["fault"]))
        eng.fault_step = extra["fault_step"]
        guards.load(extra.get("guards"))
        reqs = {rec["rid"]: Request(rec["rid"],
                                    np.asarray(rec["prompt"], np.int32),
                                    rec["max_new"], out=list(rec["out"]),
                                    done=rec["done"],
                                    deadline_ms=rec.get("deadline_ms"),
                                    submit_t=rec.get("submit_t", 0.0),
                                    level=rec.get("level", 0),
                                    attempts=rec.get("attempts", 0),
                                    status=rec.get("status", "ok"))
                for rec in extra["requests"]}
        self.queue = [reqs[rid] for rid in extra["queue"]]
        self._next_rid = extra["next_rid"]
        self.events = [tuple(e) for e in extra["events"]]
        # a snapshot from before a counter existed restores it at 0
        self.stats = {**_FRESH_STATS, **extra["stats"]}
        self.statuses = {int(k): v
                         for k, v in extra.get("statuses", {}).items()}
        st = _RunState(
            gen=GenerationConfig(**extra["gen"]),
            cap_budget=extra["cap_budget"],
            key=tree["key"],
            slots=[None if rec is None
                   else _Slot(req=reqs[rec["rid"]], budget=rec["budget"],
                              seq=rec.get("seq", 0))
                   for rec in extra["slots"]],
            tok=np.array(jax.device_get(tree["tok"]), np.int32),
            pos=np.array(jax.device_get(tree["pos"]), np.int64),
            active=np.array(jax.device_get(tree["active"]), bool),
            step=extra["step"],
            results={int(k): np.asarray(v, np.int32)
                     for k, v in extra["results"].items()},
            level=np.array(jax.device_get(tree["level"]), np.int32))
        self._state = st
        log.info("resumed serve drain from step %d (%d in flight, %d queued)",
                 ck_step, sum(s is not None for s in st.slots),
                 len(self.queue))
        return self._drive(st, on_complete=on_complete, max_steps=max_steps)


class ServeSupervisor:
    """Checkpoint-restore supervision of a serve drain, one host.

    ``make_batcher()`` builds a fresh :class:`DurableBatcher` over a fresh
    engine — the "restarted process".  The supervisor heartbeats the monitor
    from the batcher's step hook; when the drive loop dies, the crashed
    process goes silent (its ``last_beat`` is rolled past ``dead_after_s`` —
    a dead process cannot beat, the rollback just skips the wall-clock wait),
    ``FailoverPolicy`` rules ELASTIC_DOWN for the dead host, and the
    supervisor restarts: fresh batcher, ``resume()`` from the last snapshot.
    ``min_hosts=0`` because serving keeps zero quorum — a lone host restarts
    rather than aborting the job.
    """

    def __init__(self, make_batcher: Callable[[], DurableBatcher], *,
                 host: str = "serve/0", dead_after_s: float = 60.0,
                 max_restarts: int = 3, clock=None):
        import time
        self.make_batcher = make_batcher
        self.host = host
        self.max_restarts = max_restarts
        self.monitor = HeartbeatMonitor(
            [host], dead_after_s=dead_after_s,
            clock=clock if clock is not None else time.monotonic)
        self.policy = FailoverPolicy(min_hosts=0)
        self.detector = StragglerDetector()
        self.restarts = 0
        self.decisions: list = []

    def _attach(self, batcher: DurableBatcher):
        prev = batcher.on_step

        def hook(step: int):
            self.monitor.beat(self.host, step)
            if prev is not None:
                prev(step)
        batcher.on_step = hook
        return batcher

    def run(self, submit: Callable[[DurableBatcher], Any],
            gen: GenerationConfig | None = None, *, key=None,
            on_complete=None) -> dict:
        """Drive a workload to completion across crashes.

        ``submit(batcher)`` enqueues the requests on the initial process;
        restarted processes inherit the queue from the snapshot instead."""
        batcher = self._attach(self.make_batcher())
        submit(batcher)
        last_step = 0
        first = True
        while True:
            try:
                if first:
                    return batcher.run(gen, on_complete=on_complete, key=key)
                return batcher.resume(on_complete=on_complete)
            except Exception as e:
                st = self.monitor.hosts[self.host]
                last_step = max(last_step, st.last_step)
                st.last_beat = (self.monitor.clock()
                                - self.monitor.dead_after_s - 1.0)
                decision = self.policy.decide(self.monitor, self.detector,
                                              last_step)
                self.decisions.append(decision)
                if (decision.action not in (Action.ELASTIC_DOWN,
                                            Action.RESTART)
                        or self.restarts >= self.max_restarts):
                    raise
                self.restarts += 1
                log.warning("serve drain died at step ~%d (%s); restart "
                            "%d/%d from last snapshot", last_step, e,
                            self.restarts, self.max_restarts)
                batcher = self._attach(self.make_batcher())
                self.monitor.beat(self.host, 0)  # new process is alive
                first = False
