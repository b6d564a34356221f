"""Open-loop load around ``serve_continuous``'s ``step_hook``.

The program submits nothing on a schedule and leaves its loop as soon as
nothing is pending or active, so this module does both from the hook,
which the loop calls after every absorbed step:

- it notes every token the first time it sees it, in an active lane's
  ``tokens`` or among the completed requests: that is the end of the step
  that produced it, and the token's delivery time;
- it submits the requests that are due (open loop), or tops the pending
  queue up (backlog), and, when nothing is pending or active, sleeps until
  the next request is due, so the loop never runs dry between arrivals;
- it opens the window once warm-up is over, closes it after the run's
  seconds, and ends the run: a draining mix lets the requests due in the
  window finish, a cancelling one cancels what is left and keeps the
  tokens each cancelled request was served, for the comparison;
- at the close, where the loop has waited for the step, it reads the
  device's bytes in use: what serving holds, apart from set-up's peak;
- in a traced run it starts and stops the profiler at step boundaries
  inside the window, where the loop has already waited for the step.

Each step of the window leaves a record of the decode lanes' context
lengths and the prompts admitted, from which the per-layer readers count
operations and bytes.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import numpy as np

DRAIN_CAP_S = 60.0


@dataclasses.dataclass
class Rec:
    """What the load generator knows about one request."""
    rid: int
    plen: int
    due: float                    # absolute host time it was due
    submitted: float = float('nan')
    times: List[float] = dataclasses.field(default_factory=list)
    status: str = 'pending'       # pending | done | failed | cancelled
    tokens: Optional[List[int]] = None
    prompt: Optional[np.ndarray] = None
    in_window: bool = False
    warm: bool = False            # a warm-up request, never sampled
    slot: Optional[int] = None    # the lane that served it (the last one)


@dataclasses.dataclass
class Step:
    """One loop step, as the hook saw it after the step."""
    t: float
    ctx: List[int]                # live context of every lane decoded
    prefills: List[int]           # prompt lengths admitted in the step
    tokens: int                   # tokens delivered by the step


class OpenLoop:
    def __init__(self, traffic, *, seconds: float, slots: int,
                 make_request: Callable, warm_steps: int = 2,
                 tracer=None, clock=time.perf_counter,
                 memory_in_use: Optional[Callable[[], int]] = None):
        self.traffic = traffic
        self.seconds = float(seconds)
        self.slots = slots
        self.make_request = make_request
        self.warm_steps = warm_steps
        self.tracer = tracer
        self.clock = clock
        self.recs: Dict[int, Rec] = {}
        self.steps: List[Step] = []
        self.phase = 'warmup'
        self.t_open = self.t_close = None
        self.n_hooks = 0
        self._seen_done = 0
        self._seen_term = dict(failed=0, rejected=0, cancelled=0)
        self._items = traffic.items()
        self._arrivals: List = []
        self._next_arrival = 0
        self.compiles_at_open = None
        self.backlog_at_close = None
        self.compile_counter = None
        self.memory_in_use = memory_in_use
        self.bytes_in_use_at_close = None

    # -- requests ------------------------------------------------------------
    @staticmethod
    def _rid(item) -> int:
        """Warm-up items count down from -1; their rids sit above every
        traffic rid."""
        return item.idx if item.idx >= 0 else 10 ** 9 - item.idx

    def _submit(self, sched, item, due: float, now: float) -> None:
        rid = self._rid(item)
        rec = Rec(rid, len(item.prompt), due, submitted=now,
                  prompt=item.prompt, in_window=self.phase == 'window')
        self.recs[rid] = rec
        req = self.make_request(rid, item.prompt, item.target_gen)
        if not sched.submit(req):
            rec.status = 'failed'

    def initial_stream(self) -> List:
        """Requests handed to ``serve_continuous`` before its first step:
        the warm-up requests, and for a backlog mix the queued backlog."""
        now = self.clock()
        items = list(self.traffic.warmup())
        if not self.traffic.open_loop:
            items += [next(self._items) for _ in range(2 * self.slots)]
        reqs = []
        for it in items:
            rid = self._rid(it)
            self.recs[rid] = Rec(rid, len(it.prompt), now, submitted=now,
                                 prompt=it.prompt, warm=it.idx < 0)
            reqs.append(self.make_request(rid, it.prompt, it.target_gen))
        return reqs

    # -- observation -----------------------------------------------------------
    def _observe(self, sched, now: float) -> Step:
        ctx, prefills, delivered = [], [], 0
        lanes = list(sched.active.items())
        lanes += [(None, st) for st in sched.completed[self._seen_done:]]
        for slot, st in lanes:
            rec = self.recs[st.req.rid]
            if slot is not None:
                rec.slot = slot
            n = len(st.tokens)
            new = n - len(rec.times)
            if new <= 0:
                continue
            if not rec.times:
                prefills.append(rec.plen)
            rec.times.extend([now] * new)
            delivered += new
            if n >= 2:
                # the decode step that produced token n attended over the
                # prompt and the n - 1 tokens before it
                ctx.append(rec.plen + n - 1)
        for st in sched.completed[self._seen_done:]:
            rec = self.recs[st.req.rid]
            rec.status = 'done'
            rec.tokens = list(st.tokens)
        self._seen_done = len(sched.completed)
        for kind in ('failed', 'rejected', 'cancelled'):
            lst = getattr(sched, kind)
            for req in lst[self._seen_term[kind]:]:
                rec = self.recs[req.rid]
                if rec.status == 'pending':
                    rec.status = 'cancelled' if kind == 'cancelled' \
                        else 'failed'
            self._seen_term[kind] = len(lst)
        return Step(now, ctx, prefills, delivered)

    # -- the hook --------------------------------------------------------------
    def hook(self, sched, kv, cache) -> None:
        del kv, cache        # no reference to device state is kept
        if self.tracer is None:
            return self._hook(sched)
        with self.tracer.annotate('bench.hook'):
            return self._hook(sched)

    def _hook(self, sched) -> None:
        now = self.clock()
        self.n_hooks += 1
        step = self._observe(sched, now)
        if self.phase == 'window':
            self.steps.append(step)
        if self.tracer is not None and self.phase == 'window':
            self.tracer.at_step(now, self.t_open, self.seconds)
        if self.phase == 'warmup' and self._warm_done(sched):
            self._open(now)
        if self.phase == 'window' and now >= self.t_open + self.seconds:
            self._close(sched, now)
        if self.phase == 'drain' and now >= self.t_close + DRAIN_CAP_S:
            self._cancel_all(sched)
        self._feed(sched, now)

    def _warm_done(self, sched) -> bool:
        if self.traffic.open_loop:
            return sched.done
        return self.n_hooks >= self.warm_steps

    def _open(self, now: float) -> None:
        self.phase = 'window'
        self.t_open = now
        if self.compile_counter is not None:
            self.compiles_at_open = self.compile_counter.count
        if self.traffic.open_loop:
            self._arrivals = self.traffic.arrivals()

    def _close(self, sched, now: float) -> None:
        self.t_close = now
        self.backlog_at_close = dict(pending=len(sched.pending),
                                     active=len(sched.active))
        if self.memory_in_use is not None:
            self.bytes_in_use_at_close = int(self.memory_in_use())
        if self.tracer is not None:
            self.tracer.stop(now)
        if self.traffic.drains:
            self.phase = 'drain'
        else:
            self.phase = 'done'
            self._cancel_all(sched)

    def _cancel_all(self, sched) -> None:
        for st in sched.active.values():
            # the served prefix: the tokens delivered before the cancel
            rec = self.recs[st.req.rid]
            rec.tokens = list(st.tokens[:len(rec.times)])
        rids = [st.req.rid for st in sched.active.values()]
        rids += [r.rid for r in sched.pending]
        for rid in rids:
            sched.cancel(rid)
            if self.recs[rid].status == 'pending':
                self.recs[rid].status = 'cancelled'
        self.phase = 'done'

    def _feed(self, sched, now: float) -> None:
        if self.phase in ('drain', 'done'):
            return
        if not self.traffic.open_loop:
            while len(sched.pending) < self.slots:
                self._submit(sched, next(self._items), now, now)
            return
        if self.phase != 'window':
            return
        while self._next_arrival < len(self._arrivals):
            it = self._arrivals[self._next_arrival]
            due = self.t_open + it.due
            if due > now:
                if not sched.done:
                    return
                # nothing to serve: wait for the request, and stamp its
                # submission when it actually goes in
                self._wait(due)
                now = self.clock()
            self._submit(sched, it, due, now)
            self._next_arrival += 1

    def _wait(self, until: float) -> None:
        ann = self.tracer.annotate('bench.wait_arrival') \
            if self.tracer is not None else None
        if ann is not None:
            ann.__enter__()
        try:
            dt = until - self.clock()
            if dt > 0:
                time.sleep(dt)
        finally:
            if ann is not None:
                ann.__exit__(None, None, None)

    # -- results ----------------------------------------------------------------
    def window_records(self) -> List[Rec]:
        return [r for r in self.recs.values() if r.in_window]

    def lateness(self) -> np.ndarray:
        return np.array([r.submitted - r.due for r in self.window_records()])
