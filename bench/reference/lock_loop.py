"""Round loop of the reference's lock family (dynamic 2PL, deadlock-free
locking, partitioned store, ORTHRUS), written from the family's
semantics as one jitted round step over every slot.

An execution lane holds ``window`` slots; a slot holds one transaction
at a time. Each round, in order:

1. admission: every empty slot takes the next transaction of the stream
   (a partition worker: the next one homed to it), and spends the fixed
   transaction cost, plus OLLP reconnaissance where planned, as lock time;
2. a slot whose post-abort back-off ran out starts its next attempt;
3. a slot done with its set-up starts acquiring (ORTHRUS: sends its
   first request message to the CC lanes, one hop);
4. ORTHRUS CC lanes admit request and release messages that arrived,
   oldest transaction first, at most ``cc_keys_per_round`` key-ops per
   lane per round; a transaction's requests for one CC lane enter the
   lock table together once all are admitted;
5. elsewhere, releases that are due free their records at once;
6. a lock-table slot that is free requests the lock at its plan
   pointer; each new request gets the next enqueue stamp;
7. the grant pass (``lockgrant``) grants pending requests per record in
   enqueue order; a slot re-requesting a record it write-holds is
   granted at once;
8. deadlock handling over the waits-for relation: a waiting request
   waits for every slot holding its record in a conflicting mode and,
   where no holder conflicts, for every conflicting request queued ahead
   of it (grants are FIFO); wait-die aborts a waiter that waits for an
   older transaction (one timestamp probe per new wait); wait-for and
   dreadlocks detect a cycle of waits, one propagation step a round, and
   abort its youngest member; their per-round costs accrue as deadlock
   time;
9. shared lock tables charge each new lock-table op the record line's
   backlog plus its service time, which grows with the line's recent
   sharers when it moves between lanes (ORTHRUS is exempt);
10. slots advance: a granted lock lets dynamic 2PL run the op (the last
    one with any ops beyond the locks); deadlock-free and partitioned
    store run every op once all locks are held; ORTHRUS moves to the
    next CC lane's group, and, once the last is granted, a response hop
    later its lane runs the oldest ready transaction. A finished
    transaction releases (ORTHRUS: one hop later); a first attempt whose
    OLLP estimate missed aborts and retries after a back-off; the rest
    commit once every lock is released, adding their latency to the
    histogram;
11. lane time: ORTHRUS counts each lane once (exec, else wait, else
    message, else idle), the others count every slot;
12. rounds in which no slot can act are leapt over.

With ``isolation`` off (the control) every pending request is granted.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .core import (ACQ, BACKOFF, CAT_DL, CAT_EXEC, CAT_IDLE, CAT_LOCK,
                   CAT_MSG, CAT_WAIT, EMPTY, EPOCH_BITS, EXEC, INIT,
                   LAT_BUCKETS, MSG, NCAT, READY, REL)
from .data import MODE_READ, MODE_WRITE
from .lockgrant import (KEY_SENTINEL, REQ_NONE, REQ_READ, REQ_RELEASE,
                        REQ_WRITE, inverse_permutation, lex_order, run_before,
                        runs, segmented_grant)

I32 = jnp.int32
IMAX = jnp.iinfo(I32).max
IMIN = jnp.iinfo(I32).min


def state0(cfg, num_records: int, T: int, K: int) -> dict:
    z = lambda *shape: jnp.zeros(shape, I32)  # noqa: E731
    f = lambda *shape: jnp.zeros(shape, jnp.bool_)  # noqa: E731
    return dict(
        r=z(), next_txn=z(), enq_ctr=jnp.ones((), I32),
        tid=jnp.full((T,), -1, I32), widx=z(T), lane_ctr=z(T), ts=z(T),
        phase=z(T), committing=f(T), busy_until=z(T), busy_kind=z(T),
        kptr=z(T), attempt=z(T), want=f(T, K), granted=f(T, K),
        enq=z(T, K), adm_done=f(T, K), rel_done=f(T, K), ccptr=z(T),
        msg_arrive=z(T), msg_stage=z(T), release_at=z(T), waited=f(T),
        dl_debt=z(T), reach=f(T, T),
        wh=jnp.full((num_records,), -1, I32), rc=z(num_records),
        # per record: the sharer-heat epoch, its count and the previous
        # epoch's count; the round its line is free and the last lane
        heat_ep=jnp.full((num_records,), -10, I32), heat_cur=z(num_records),
        heat_prev=z(num_records), line_free=z(num_records),
        line_lane=jnp.full((num_records,), -1, I32),
        commits=z(), aborts_dl=z(), aborts_ollp=z(), wasted=z(),
        cat=z(NCAT), steps=z(), arrive=z(T), lat_hist=z(LAT_BUCKETS),
    )


def _at(a, col):
    """a[t, col[t]] for each slot t."""
    return jnp.take_along_axis(a, col[:, None], axis=1)[:, 0]


def make_step(cfg, meta):
    m = cfg.cost
    T, K, R, N = cfg.n_slots, meta.max_keys, meta.num_records, meta.n_txns
    W, lanes, n_cc = cfg.window, cfg.n_exec, max(cfg.n_cc, 1)
    cc = cfg.is_orthrus
    dl = cfg.deadlock_scheme
    lane = jnp.arange(T, dtype=I32) // W
    slot = jnp.arange(T, dtype=I32)
    col = jnp.arange(K, dtype=I32)

    def rounds(cycles):
        return (cycles + m.cycles_per_round - 1) // m.cycles_per_round

    lock_cycles = (m.partition_lock_cycles if cfg.partition_locks
                   else m.lock_op_cycles)
    op_rounds = rounds(m.exec_op_cycles + (
        0 if cfg.partition_locks or cfg.split_index
        else m.shared_index_penalty_cycles))
    wait_cycles = {"waitfor": m.waitfor_maintain_cycles,
                   "dreadlocks": m.dreadlocks_spin_cycles}.get(dl, 0)
    hop = m.msg_hop_rounds

    def step(p, s, r_end):
        s = dict(s)
        r = s["r"]
        streams = meta.lane_cols > 0

        def txn():
            w = jnp.where(s["tid"] >= 0, s["widx"] % N, 0)
            return dict(keys=p["keys"][w], modes=p["modes"][w],
                        cc=p["part"][w] % n_cc, nkeys=p["nkeys"][w],
                        ops=p["exec_ops"][w], ollp=p["ollp"][w],
                        miss=p["ollp_miss"][w])

        def clear(mask):
            for k in ("want", "granted", "adm_done", "rel_done"):
                s[k] = s[k] & ~mask[:, None]
            for k in ("kptr", "ccptr"):
                s[k] = jnp.where(mask, 0, s[k])
            s["waited"] = s["waited"] & ~mask

        def set_busy(mask, until, kind):
            s["busy_until"] = jnp.where(mask, until, s["busy_until"])
            s["busy_kind"] = jnp.where(mask, kind, s["busy_kind"])

        # 1. admission
        empty = s["phase"] == EMPTY
        if streams:
            cand = p["lane_stream"][slot, s["lane_ctr"] % meta.lane_cols]
            adm = empty & (cand >= 0)
            tid = s["lane_ctr"] * T + slot
            s["widx"] = jnp.where(adm, cand, s["widx"])
            s["lane_ctr"] = s["lane_ctr"] + adm.astype(I32)
        else:
            adm = empty
            tid = s["next_txn"] + jnp.cumsum(empty.astype(I32)) - 1
            s["widx"] = jnp.where(adm, tid % N, s["widx"])
        s["next_txn"] = s["next_txn"] + adm.sum(dtype=I32)
        for k, v in (("tid", tid), ("ts", tid), ("attempt", 0),
                     ("arrive", r), ("phase", INIT)):
            s[k] = jnp.where(adm, v, s[k])
        x = txn()
        set_busy(adm, r + rounds(m.txn_fixed_cycles + jnp.where(
            x["ollp"], m.recon_cycles, 0)), CAT_LOCK)
        clear(adm)
        kvalid = col[None, :] < x["nkeys"][:, None]

        # 2. back-off over: the next attempt
        again = (s["phase"] == BACKOFF) & (s["busy_until"] <= r)
        s["phase"] = jnp.where(again, INIT, s["phase"])
        set_busy(again, r + rounds(m.txn_fixed_cycles), CAT_LOCK)
        clear(again)
        s["attempt"] = s["attempt"] + again.astype(I32)
        free = s["busy_until"] <= r

        # 3. set-up done: start acquiring
        go = (s["phase"] == INIT) & free & (s["tid"] >= 0)
        if cc:
            s["phase"] = jnp.where(go, MSG, s["phase"])
            s["msg_stage"] = jnp.where(go, 0, s["msg_stage"])
            s["msg_arrive"] = jnp.where(go, r + hop, s["msg_arrive"])
        else:
            s["phase"] = jnp.where(go, ACQ, s["phase"])

        def group():
            """Key columns of each slot's current CC-lane group."""
            head = _at(x["cc"], jnp.minimum(s["ccptr"], K - 1))
            return ((col[None, :] >= s["ccptr"][:, None]) & kvalid
                    & (x["cc"] == head[:, None]))

        released = jnp.zeros((T, K), jnp.bool_)

        def release(entries):
            k = jnp.where(entries, x["keys"], R)
            wr = entries & (x["modes"] == MODE_WRITE)
            rd = entries & (x["modes"] == MODE_READ)
            s["wh"] = s["wh"].at[jnp.where(wr, k, R)].set(-1, mode="drop")
            s["rc"] = s["rc"].at[jnp.where(rd, k, R)].add(-1, mode="drop")
            s["granted"] = s["granted"] & ~entries

        if cc:
            # 4. CC lanes admit arrived messages, oldest txn first
            grp = group()
            asking = ((s["phase"] == MSG) & (s["msg_stage"] == 0)
                      & (s["msg_arrive"] <= r))
            acq = asking[:, None] & grp & ~s["adm_done"]
            leaving = (s["phase"] == REL) & (s["release_at"] <= r)
            rel = leaving[:, None] & s["granted"] & ~s["rel_done"]
            act = (acq | rel).reshape(-1)
            lane_of_op = jnp.where(act, x["cc"].reshape(-1), n_cc)
            ts_of_op = jnp.broadcast_to(s["ts"][:, None], (T, K)).reshape(-1)
            order = lex_order(lane_of_op, ts_of_op)
            place = run_before(jnp.ones((T * K,), I32),
                                runs(lane_of_op[order]))
            taken = (place[inverse_permutation(order)].reshape(T, K)
                     < m.cc_keys_per_round) & (acq | rel)
            s["adm_done"] = s["adm_done"] | (taken & acq)
            in_table = asking & jnp.where(grp, s["adm_done"], True).all(1)
            new_want = in_table[:, None] & grp
            s["phase"] = jnp.where(in_table, ACQ, s["phase"])
            release(taken & rel)
            s["rel_done"] = s["rel_done"] | (taken & rel)
            s["want"] = s["want"] | new_want
        else:
            # 5. due releases free their records
            released = (((s["phase"] == REL) & (s["release_at"] <= r))[:, None]
                        & s["granted"])
            release(released)
            # 6. a free acquiring slot requests the lock at its pointer
            new_want = (((s["phase"] == ACQ) & free)[:, None]
                        & (col[None, :] == s["kptr"][:, None]) & kvalid
                        & ~s["granted"] & ~s["want"])
            s["want"] = s["want"] | new_want
        flat_new = new_want.reshape(-1)
        n_new = flat_new.sum(dtype=I32)
        stamp = (s["enq_ctr"] + jnp.cumsum(flat_new.astype(I32)) - 1)
        s["enq"] = jnp.where(new_want, stamp.reshape(T, K), s["enq"])

        # 7. grant pass over pending requests and this round's releases
        pend = s["want"] & ~s["granted"] & (s["phase"] == ACQ)[:, None]
        kind = jnp.where(pend, jnp.where(x["modes"] == MODE_WRITE,
                                         REQ_WRITE, REQ_READ),
                         jnp.where(released, REQ_RELEASE, REQ_NONE))
        ekey = jnp.where(pend | released, x["keys"], KEY_SENTINEL).reshape(-1)
        rel_stamp = (s["enq_ctr"] + n_new
                     + jnp.arange(T * K, dtype=I32)).reshape(T, K)
        estamp = jnp.where(released, rel_stamp, s["enq"]).reshape(-1)
        s["enq_ctr"] = s["enq_ctr"] + n_new + released.sum(dtype=I32)
        rec = jnp.minimum(ekey, R - 1)
        known = ekey < R
        order = lex_order(ekey, estamp)
        back = inverse_permutation(order)
        g, contend, _ = segmented_grant(
            ekey[order], estamp[order], kind.reshape(-1)[order],
            ((s["wh"][rec] == -1) & known)[order],
            jnp.where(known, s["rc"][rec], 0)[order])
        own = ((kind.reshape(-1) == REQ_READ) | (kind.reshape(-1) == REQ_WRITE)
               ) & known & (s["wh"][rec] == jnp.repeat(slot, K))
        grant = (g[back] | own).reshape(T, K)
        if not cfg.isolation:
            grant = grant | pend
        contend = contend[back].reshape(T, K)
        gk = jnp.where(grant, x["keys"], R)
        gw = grant & (x["modes"] == MODE_WRITE)
        gr = grant & (x["modes"] == MODE_READ)
        s["wh"] = s["wh"].at[jnp.where(gw, gk, R)].set(
            jnp.broadcast_to(slot[:, None], (T, K)), mode="drop")
        s["rc"] = s["rc"].at[jnp.where(gr, gk, R)].add(1, mode="drop")
        s["granted"] = s["granted"] | grant

        # 8. deadlock handling
        kp = jnp.minimum(s["kptr"], K - 1)
        dies = jnp.zeros((T,), jnp.bool_)
        if dl != "none":
            pending = pend & ~grant
            waiting = _at(pending, kp)
            wkey = jnp.where(waiting, _at(x["keys"], kp), KEY_SENTINEL)
            wwrite = _at(x["modes"], kp) == MODE_WRITE
            # on[t, u, c]: u's entry c is on the record t waits for, in a
            # mode that conflicts with t's request
            on = ((x["keys"][None, :, :] == wkey[:, None, None])
                  & (wwrite[:, None, None]
                     | (x["modes"] == MODE_WRITE)[None, :, :]))
            other = ((slot[None, :] != slot[:, None])
                     & (s["tid"] >= 0)[None, :] & waiting[:, None])
            holds = (on & s["granted"][None, :, :]).any(-1) & other
            ahead = (on & pending[None, :, :] & (
                s["enq"][None, :, :] < _at(s["enq"], kp)[:, None, None])
            ).any(-1) & other
            # waits_on[t, u]: u holds t's record in a conflicting mode, or,
            # where no holder conflicts, u's conflicting request is queued
            # ahead of t's
            waits_on = holds | (ahead & ~holds.any(1)[:, None])
            if dl == "waitdie":
                dies = (waits_on & (s["ts"][None, :] < s["ts"][:, None])
                        ).any(1)
                s["dl_debt"] = s["dl_debt"] + jnp.where(
                    waiting & ~s["waited"], m.waitdie_check_cycles, 0)
            else:
                ident = jnp.eye(T, dtype=jnp.bool_)
                step_reach = ident | (waits_on[:, :, None]
                                      & s["reach"][None, :, :]).any(1)
                s["reach"] = jnp.where(waiting[:, None], step_reach, ident)
                cyc = (waits_on & s["reach"].T).any(1)
                both = s["reach"] & s["reach"].T
                youngest = jnp.where(both & cyc[None, :], s["ts"][None, :],
                                     -1).max(1)
                dies = cyc & (s["ts"] >= youngest)
                s["dl_debt"] = s["dl_debt"] + jnp.where(waiting, wait_cycles,
                                                        0)
            s["waited"] = waiting
            debt = s["dl_debt"] // m.cycles_per_round
            set_busy(debt > 0, jnp.maximum(s["busy_until"], r) + debt,
                     CAT_DL)
            s["dl_debt"] = s["dl_debt"] % m.cycles_per_round
            dies = dies & waiting
            s["aborts_dl"] = s["aborts_dl"] + dies.sum(dtype=I32)
            s["wasted"] = s["wasted"] + jnp.where(dies, s["kptr"], 0).sum(
                dtype=I32)
            s["phase"] = jnp.where(dies, REL, s["phase"])
            s["committing"] = s["committing"] & ~dies
            s["release_at"] = jnp.where(dies, r, s["release_at"])
            s["want"] = s["want"] & ~dies[:, None]

        # 9. lock-table lines: backlog, service time, sharer heat
        if not cc:
            op = new_want | released
            mut = op & ~dies[:, None]
            epoch = r >> EPOCH_BITS
            k = jnp.minimum(jnp.where(op, x["keys"], 0), R - 1)
            ep, cur, prev = s["heat_ep"][k], s["heat_cur"][k], s["heat_prev"][k]
            sharers = jnp.where(ep == epoch, jnp.maximum(prev, cur),
                                jnp.where(ep == epoch - 1, cur, 0))
            moved = s["line_lane"][k] != lane[:, None]
            coh = jnp.where(moved, m.coherence_cycles_per_sharer
                            * jnp.clip(sharers, 1, lanes - 1), 0)
            if dl == "dreadlocks":
                coh = coh + m.dreadlocks_spin_cycles * jnp.maximum(
                    contend - 1, 0)
            dur = rounds(lock_cycles + coh)
            lfree = s["line_free"][k]
            backlog = jnp.maximum(jnp.where(mut, lfree - r, 0), 0)
            charge = jnp.where(op, backlog + dur, 0).sum(1)
            tk = jnp.where(op, k, R)

            def per_record(mask):
                n = jnp.zeros((R + 1,), I32).at[jnp.where(mask, k, R)].add(1)
                return n[k]

            until = jnp.maximum(lfree, r) + jnp.where(mut, per_record(mut)
                                                      * dur, 0)
            s["line_free"] = s["line_free"].at[tk].max(
                jnp.where(mut, until, IMIN), mode="drop")
            s["line_lane"] = s["line_lane"].at[tk].max(
                jnp.broadcast_to(lane[:, None], (T, K)), mode="drop")
            s["heat_prev"] = s["heat_prev"].at[tk].set(
                jnp.where(ep == epoch, prev, jnp.where(ep == epoch - 1,
                                                       cur, 0)),
                mode="drop")
            s["heat_cur"] = s["heat_cur"].at[tk].set(
                jnp.where(ep == epoch, cur, 0) + per_record(op), mode="drop")
            s["heat_ep"] = s["heat_ep"].at[tk].set(
                jnp.broadcast_to(epoch, (T, K)), mode="drop")
            set_busy(charge > 0, jnp.maximum(s["busy_until"], r) + charge,
                     CAT_LOCK)

        # 10. advance
        free = s["busy_until"] <= r
        if cfg.is_dynamic_2pl:
            go = (s["phase"] == ACQ) & free & _at(s["granted"], kp) & ~dies
            last = go & (s["kptr"] + 1 >= x["nkeys"])
            extra = jnp.maximum(x["ops"] - x["nkeys"], 0)
            set_busy(go, jnp.maximum(s["busy_until"], r) + op_rounds
                     + jnp.where(last, extra * op_rounds, 0), CAT_EXEC)
            s["kptr"] = s["kptr"] + go.astype(I32)
            s["phase"] = jnp.where(last, EXEC, s["phase"])
        elif not cc:
            go = (s["phase"] == ACQ) & free & _at(s["granted"], kp)
            s["kptr"] = s["kptr"] + go.astype(I32)
            run = go & (s["kptr"] >= x["nkeys"])
            s["phase"] = jnp.where(run, EXEC, s["phase"])
            set_busy(run, jnp.maximum(s["busy_until"], r)
                     + x["ops"] * op_rounds, CAT_EXEC)
        else:
            grp = group()
            held = (s["phase"] == ACQ) & jnp.where(grp, s["granted"],
                                                   True).all(1)
            nxt = jnp.where((col[None, :] >= s["ccptr"][:, None]) & kvalid
                            & ~grp, col[None, :], K).min(1)
            more = held & (nxt < K)
            s["ccptr"] = jnp.where(more, nxt, s["ccptr"])
            s["adm_done"] = s["adm_done"] & ~more[:, None]
            s["phase"] = jnp.where(held, MSG, s["phase"])
            s["msg_stage"] = jnp.where(held, jnp.where(more, 0, 1),
                                       s["msg_stage"])
            s["msg_arrive"] = jnp.where(held, r + hop, s["msg_arrive"])
            back_msg = ((s["phase"] == MSG) & (s["msg_stage"] == 1)
                        & (s["msg_arrive"] <= r))
            s["phase"] = jnp.where(back_msg, READY, s["phase"])
            running = jax.ops.segment_sum(
                ((s["phase"] == EXEC) & ~free).astype(I32), lane,
                num_segments=lanes)
            ready = s["phase"] == READY
            oldest = jax.ops.segment_min(jnp.where(ready, s["ts"], IMAX),
                                         lane, num_segments=lanes)
            start = ready & (s["ts"] == oldest[lane]) & (running[lane] == 0)
            s["phase"] = jnp.where(start, EXEC, s["phase"])
            set_busy(start, r + x["ops"] * op_rounds, CAT_EXEC)
        fin = (s["phase"] == EXEC) & (s["busy_until"] <= r)
        missed = fin & x["miss"] & (s["attempt"] == 0)
        s["aborts_ollp"] = s["aborts_ollp"] + missed.sum(dtype=I32)
        s["wasted"] = s["wasted"] + jnp.where(missed, x["ops"], 0).sum(
            dtype=I32)
        s["phase"] = jnp.where(fin, REL, s["phase"])
        s["committing"] = jnp.where(fin, ~missed, s["committing"])
        s["release_at"] = jnp.where(fin, r + (hop if cc else 0),
                                    s["release_at"])
        s["rel_done"] = s["rel_done"] & ~fin[:, None]
        s["want"] = s["want"] & ~fin[:, None]
        out = ((s["phase"] == REL) & (s["release_at"] <= r)
               & ~s["granted"].any(1))
        com = out & s["committing"]
        s["commits"] = s["commits"] + com.sum(dtype=I32)
        lat = r - s["arrive"]
        bucket = sum((lat >= (1 << b)).astype(I32)
                     for b in range(LAT_BUCKETS - 1))
        s["lat_hist"] = s["lat_hist"].at[jnp.where(com, bucket, LAT_BUCKETS)
                                         ].add(1, mode="drop")
        s["phase"] = jnp.where(out, jnp.where(s["committing"], EMPTY,
                                              BACKOFF), s["phase"])
        s["tid"] = jnp.where(com, -1, s["tid"])
        s["busy_until"] = jnp.where(out & ~s["committing"],
                                    r + m.abort_backoff_rounds,
                                    s["busy_until"])
        s["want"] = s["want"] & ~out[:, None]

        # 11. lane time
        busy = s["busy_until"] > r
        ph = s["phase"]
        what = jnp.where(
            busy, s["busy_kind"],
            jnp.where((ph == ACQ) & (s["want"] & ~s["granted"]).any(1),
                      CAT_WAIT,
                      jnp.where((ph == MSG) | (ph == READY) | (ph == REL),
                                CAT_MSG, CAT_IDLE)))
        if cc:
            def any_in_lane(mask):
                return jax.ops.segment_max(mask.astype(I32), lane,
                                           num_segments=lanes) == 1

            lane_what = jnp.where(
                any_in_lane(busy & (what == CAT_EXEC)), CAT_EXEC,
                jnp.where(any_in_lane(what == CAT_WAIT), CAT_WAIT,
                          jnp.where(any_in_lane(what == CAT_MSG), CAT_MSG,
                                    CAT_IDLE)))
            counts = jnp.zeros((NCAT,), I32).at[lane_what].add(1)
        else:
            counts = jnp.zeros((NCAT,), I32).at[what].add(1)

        # 12. leap to the next round in which a slot can act
        if cfg.event_leap:
            nxt = jnp.where(busy, s["busy_until"], IMAX)
            nxt = jnp.minimum(nxt, jnp.where(
                (ph == MSG) & (s["msg_arrive"] > r), s["msg_arrive"], IMAX))
            nxt = jnp.minimum(nxt, jnp.where(
                (ph == REL) & (s["release_at"] > r), s["release_at"], IMAX))
            can_admit = (
                p["lane_stream"][slot, s["lane_ctr"] % meta.lane_cols] >= 0
                if streams else True)
            now = (((ph == EMPTY) & can_admit)
                   | ((ph == MSG) & (s["msg_arrive"] <= r))
                   | ((ph == REL) & (s["release_at"] <= r))
                   | (~busy & ((ph == INIT) | (ph == BACKOFF))))
            if cc:
                running = jax.ops.segment_max(
                    ((ph == EXEC) & busy).astype(I32), lane,
                    num_segments=lanes)
                now = now | ((ph == READY) & (running[lane] == 0))
            else:
                blocked = _at(s["want"] & ~s["granted"],
                              jnp.minimum(s["kptr"], K - 1))
                now = now | ((ph == ACQ) & ~busy & ~blocked)
            if dl in ("waitfor", "dreadlocks"):
                now = now | s["waited"].any()
            nxt = jnp.clip(jnp.where(now, r + 1, nxt).min(), r + 1, r_end)
        else:
            nxt = r + 1
        s["cat"] = s["cat"] + counts * (nxt - r)
        s["steps"] = s["steps"] + 1
        s["r"] = nxt
        return s

    return step
