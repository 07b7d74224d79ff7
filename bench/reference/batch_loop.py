"""Serial round loop of the batch family (dgcc, quecc and any other
protocol whose planner gives each transaction its predecessors).

Written in plain Python from the family's semantics, one slot at a time:

* batches open in turn (after the last, the first again, its commit
  marks cleared); batch b's plan is ready ``plan_rounds[b]`` rounds after
  the previous batch's plan was;
* an execution lane holds ``window`` slots. An empty slot takes the next
  txn of the open batch, in serial order, once the batch's plan is ready,
  and spends ``txn_fixed + npred * dep_check`` cycles (costed as lock
  time) before one message hop fetches its entry (costed as message
  time);
* a fetched txn waits (wait time) until every planned predecessor has
  committed; a lane runs one txn at a time, the oldest ready one first,
  for ``exec_ops`` stored-procedure ops (exec time), and it commits the
  round it finishes;
* each round a lane is counted once: exec if a slot executes, else wait
  if one waits, else msg if one awaits its hop, else idle. A commit adds
  its latency (commit round - admission round) to the histogram bucket
  of the powers of two it reaches;
* rounds in which nothing can change are leapt over, to the next timer
  or the end of the chunk, and counted as the rounds they span; ``steps``
  counts the rounds actually stepped.

With ``isolation`` off (the control) predecessors are never waited for.
"""

from __future__ import annotations

from .core import (CAT_EXEC, CAT_IDLE as IDLE, CAT_LOCK as LOCK,
                   CAT_MSG as MSG_CAT, CAT_WAIT as WAIT, LAT_BUCKETS, NCAT)
from .plans import batch_bounds

EMPTY, INIT, MSG, READY, EXEC = range(5)
NEVER = 2**31 - 1


def latency_bucket(lat: int) -> int:
    """Number of powers of two 1, 2, 4, ... 2**22 that ``lat`` reaches."""
    return sum(1 for k in range(LAT_BUCKETS - 1) if lat >= 1 << k)


class BatchLoop:
    def __init__(self, cfg, plan):
        m = cfg.cost
        self.window, self.lanes = cfg.window, cfg.n_exec
        self.isolation = cfg.isolation
        self.leap = cfg.event_leap
        n = len(plan.nkeys)
        self.exec_ops = [int(x) for x in plan.exec_ops]
        self.preds = plan.preds
        self.batches = batch_bounds(n, plan.epoch)
        self.plan_rounds = []
        for start, size in self.batches:
            ops = sum(int(x) for x in plan.nkeys[start:start + size])
            recon = sum(1 for x in plan.ollp[start:start + size] if x)
            work = ops * m.batch_plan_cycles_per_op + recon * m.recon_cycles
            self.plan_rounds.append(
                int(m.rounds(work // max(cfg.n_cc, 1))))
        self.init_rounds = [
            int(m.rounds(m.txn_fixed_cycles + len(p) * m.dep_check_cycles))
            for p in self.preds]
        self.hop = m.msg_hop_rounds
        self.op_rounds = int(m.rounds(
            m.exec_op_cycles + (0 if cfg.split_index
                                else m.shared_index_penalty_cycles)))
        T = cfg.n_slots
        self.r = 0
        self.next_txn = 0
        self.cur = 0
        self.bpos = 0
        self.left = self.batches[0][1]
        self.plan_ready = self.plan_rounds[0]
        self.done = [False] * n
        self.phase = [EMPTY] * T
        self.txn = [0] * T  # position in the stream of the slot's txn
        self.ts = [0] * T
        self.busy_until = [0] * T
        self.busy_cat = [IDLE] * T
        self.hop_at = [0] * T
        self.arrive = [0] * T
        self.commits = 0
        self.steps = 0
        self.cat = [0] * NCAT
        self.lat_hist = [0] * LAT_BUCKETS

    def counters(self) -> dict:
        return dict(commits=self.commits, aborts_dl=0, aborts_ollp=0,
                    wasted=0, next_txn=self.next_txn, steps=self.steps,
                    cat=list(self.cat), lat_hist=list(self.lat_hist))

    def _deps_done(self, s: int) -> bool:
        return not self.isolation or all(self.done[q]
                                         for q in self.preds[self.txn[s]])

    def run_to(self, r_end: int) -> None:
        while self.r < r_end:
            self.step(r_end)

    def step(self, r_end: int) -> None:
        r, W, T = self.r, self.window, len(self.phase)
        # batch rollover
        if self.left == 0:
            self.cur = (self.cur + 1) % len(self.batches)
            start, size = self.batches[self.cur]
            for t in range(start, start + size):
                self.done[t] = False
            self.bpos, self.left = start, size
            self.plan_ready += self.plan_rounds[self.cur]
        start, size = self.batches[self.cur]
        end = start + size
        # admission, in slot order
        rank = 0
        for s in range(T):
            if self.phase[s] != EMPTY:
                continue
            pos = self.bpos + rank
            if pos < end and r >= self.plan_ready:
                self.txn[s] = pos
                self.ts[s] = self.next_txn + rank
                self.arrive[s] = r
                self.phase[s] = INIT
                self.busy_until[s] = r + self.init_rounds[pos]
                self.busy_cat[s] = LOCK
            rank += 1
        admitted = min(rank, max(0, end - self.bpos)) if (
            r >= self.plan_ready) else 0
        self.bpos += admitted
        self.next_txn += admitted
        # dependency checks done: one hop to fetch the entry
        for s in range(T):
            if self.phase[s] == INIT and self.busy_until[s] <= r:
                self.phase[s] = MSG
                self.hop_at[s] = r + self.hop
            if self.phase[s] == MSG and self.hop_at[s] <= r:
                self.phase[s] = READY
        # each idle lane starts its oldest ready txn whose preds committed
        for lane in range(self.lanes):
            slots = range(lane * W, lane * W + W)
            if any(self.phase[s] == EXEC and self.busy_until[s] > r
                   for s in slots):
                continue
            ready = [s for s in slots
                     if self.phase[s] == READY and self._deps_done(s)]
            if ready:
                s = min(ready, key=lambda s: self.ts[s])
                self.phase[s] = EXEC
                self.busy_until[s] = (
                    r + self.exec_ops[self.txn[s]] * self.op_rounds)
                self.busy_cat[s] = CAT_EXEC
        # commits
        for s in range(T):
            if self.phase[s] == EXEC and self.busy_until[s] <= r:
                self.done[self.txn[s]] = True
                self.commits += 1
                self.lat_hist[latency_bucket(r - self.arrive[s])] += 1
                self.left -= 1
                self.phase[s] = EMPTY
        # how each lane spent the round
        lane_cat = []
        for lane in range(self.lanes):
            cats = set()
            for s in range(lane * W, lane * W + W):
                if self.busy_until[s] > r:
                    cats.add(self.busy_cat[s])
                elif self.phase[s] == MSG:
                    cats.add(MSG_CAT)
                elif self.phase[s] == READY:
                    cats.add(WAIT)
            lane_cat.append(next(
                (c for c in (CAT_EXEC, WAIT, MSG_CAT) if c in cats), IDLE))
        nxt = self._next_event(r, end) if self.leap else r + 1
        nxt = min(max(nxt, r + 1), r_end)
        for c in lane_cat:
            self.cat[c] += nxt - r
        self.steps += 1
        self.r = nxt

    def _next_event(self, r: int, end: int) -> int:
        """The first round after ``r`` in which anything can change."""
        W = self.window
        nxt = NEVER
        for s in range(len(self.phase)):
            ph = self.phase[s]
            if self.busy_until[s] > r:
                nxt = min(nxt, self.busy_until[s])
            elif ph == INIT:
                return r + 1
            if ph == MSG:
                if self.hop_at[s] <= r:
                    return r + 1
                nxt = min(nxt, self.hop_at[s])
            if ph == READY and self._deps_done(s) and not any(
                    self.phase[u] == EXEC and self.busy_until[u] > r
                    for u in range(s // W * W, s // W * W + W)):
                return r + 1
        if EMPTY in self.phase:
            if self.left == 0:
                return r + 1
            if self.bpos < end:
                nxt = min(nxt, max(self.plan_ready, r + 1))
        return nxt
