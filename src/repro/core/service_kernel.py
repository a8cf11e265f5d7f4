"""Batched event-synchronous service kernel (the Fig. 8 loop in JAX).

``service.BatchService`` replays the paper's batch-computing service one
heap event at a time in Python, which caps bag sizes at ~10^2 jobs.  This
module re-expresses that exact event loop as a single jitted
``lax.while_loop`` over fixed-shape state vectors:

  * ``(J,)`` job state — done work, finish time, failure/attempt counts,
    admission verdicts;
  * ``(V,)`` VM-slot state — launch time, sampled lifetime, running job,
    hot-spare expiry, per-event sequence numbers, fractional capacity.

Each loop iteration advances the simulation by ONE logical step: either a
*scheduling step* (one iteration of the serial loop's greedy ``assign``:
reuse an approved hot spare / launch a fresh VM / reject on a missed
deadline / release an idle spare / block head-of-line) or an *event step*
(the next finish / preempt / expire, chosen as the lexicographic
``(time, seq)`` minimum over per-slot candidates — the same global-seq
tiebreaker that orders the serial loop's heap keys).  All per-event work is
O(V) gathers/scatters, so the wall-clock per event is flat in J; a leading
``(B,)`` batch axis vmaps whole (scenario x policy x cluster_size x seed)
grids into one dispatch, with per-lane ``table_index`` / ``pool_index`` /
``bag_index`` gathers into deduplicated tensors (the PR-4 leading-axis
convention of ``engine.simulate_makespan_batch``).

Bit-exactness contract
----------------------
Under ``jax.enable_x64(True)`` and a shared lifetime pool, a lane is
bit-identical to ``service.BatchService.run`` — per-job completion times,
failure/attempt counts, ``vm_hours`` and the cost accounting all match the
serial heap loop float-for-float.  This holds because every arithmetic
expression (segment times, checkpoint banking, ``ReuseTable.decide``'s
index arithmetic, the VM-hours accumulation *order*) is mirrored exactly,
and because the event order is: the serial heap pops by ``(time, seq)``;
the kernel takes the same minimum over *live* candidates.  Stale heap
entries (a finish event of a preempted job, an expire event of a re-used
spare) are no-ops in the serial loop and simply never become candidates
here, with one documented exception: a hot spare that is re-used and
becomes idle again within 1e-9 h of its previous idle period would, in the
serial loop, be expired by the *older* event; the kernel only tracks the
latest expiry.  No such schedule is reachable with positive job lengths.

New policy branches (kernel-only)
---------------------------------
* Deadline admission control: a job whose estimated completion
  (``start + segment/capacity``) misses its deadline is *rejected* at
  scheduling time — before a lifetime is drawn or a VM launched.
* VM deflation (arXiv:2006.00508): with ``deflate=True`` a lane converts
  the first preemption of a *running* VM into a capacity degradation to
  ``deflate_factor`` (the remaining segment stretches by ``1/factor`` and a
  fresh lifetime is drawn for the survivor) instead of a kill; checkpoint
  banking on a later real preemption counts work-equivalent progress
  ``att_w0 + (now - att_start) * capacity``.  Idle spares are never
  deflated — reclaiming an idle VM costs no work.

Sequence-number semantics (what makes ties serial-exact): launching pushes
``seq_p`` then starting the job pushes ``seq_f = seq_p + 1``, so a VM whose
lifetime exactly equals its segment is preempted first, exactly like the
serial heap.  Finishing allocates ``seq_e`` for the hot-spare expiry;
deflation allocates a fresh ``seq_p`` for the survivor's next preemption.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import distributions as dists_mod
from . import engine
from .service import (HOT_SPARE_HOURS, PRICES_ON_DEMAND, PRICES_PREEMPTIBLE,
                      RELAUNCH_OVERHEAD, Job, ServiceResult)

POLICY_MODEL = 0
POLICY_MEMORYLESS = 1
POLICY_CODES = {"model": POLICY_MODEL, "memoryless": POLICY_MEMORYLESS}

_BIG = 2 ** 30  # int sentinel > any seq/ord the loop can allocate


def split_policy(name: str) -> tuple[str, bool]:
    """``"model+deflate"`` -> ``("model", True)``; validates the base."""
    base, _, mod = name.partition("+")
    if base not in POLICY_CODES or mod not in ("", "deflate"):
        raise ValueError(f"unknown service policy {name!r}; expected "
                         f"{sorted(POLICY_CODES)} with optional '+deflate'")
    return base, mod == "deflate"


# ---------------------------------------------------------------------------
# pooled lifetime streams
# ---------------------------------------------------------------------------

def draw_service_pool_batch(dists, seeds, *, size: int = 4096) -> np.ndarray:
    """One ``(Q, size)`` tensor of service lifetime pools in ONE device call.

    Entry ``q`` is bit-identical (x64) to ``service.draw_service_pool(
    dists[q], seed=seeds[q], size=size)`` — the uniforms come from the same
    per-seed ``default_rng(seed).uniform(size)`` reference streams (drawn
    once per *unique* seed, fanned out with a device-side gather, exactly
    like ``engine.draw_lifetime_pool_batch``) and the inversion goes through
    the same shared ``engine.capped_icdf_draw`` kernel on leaf-normalized
    parameters.
    """
    dists = list(dists)
    seeds = [int(s) for s in seeds]
    if len(dists) != len(seeds):
        raise ValueError(f"dists ({len(dists)}) and seeds ({len(seeds)}) "
                         "must align")
    dtype = jnp.result_type(float)
    norm = [jax.tree_util.tree_map(lambda l: jnp.asarray(l, dtype), d)
            for d in dists]
    eff = [d.effective() if hasattr(d, "effective") else d for d in norm]
    # uniforms per unique seed, gathered per entry on device
    uniq: dict[int, int] = {}
    blocks = []
    for s in seeds:
        if s not in uniq:
            uniq[s] = len(blocks)
            blocks.append(np.random.default_rng(s).uniform(size=size))
    u = jnp.take(jnp.asarray(np.stack(blocks), dtype),
                 jnp.asarray([uniq[s] for s in seeds]), axis=0)
    d_b = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls)[:, None], *eff)
    fl = jnp.asarray(np.array([[float(d.cdf(d.L))] for d in eff]), dtype)
    L = jnp.asarray(np.array([[float(d.L)] for d in eff]), dtype)
    return np.asarray(engine.capped_icdf_draw(d_b, u, fl, L))


# ---------------------------------------------------------------------------
# the single-lane kernel (vmapped over the (B,) lane axis)
# ---------------------------------------------------------------------------

def _setv(arr, idx, val, flag):
    """Masked scatter: ``arr[idx] = val`` iff ``flag`` (lane-select safe).

    The masking redirects the index out of bounds and relies on scatter's
    ``mode="drop"`` instead of re-reading ``arr[idx]``, and the scatter
    promises ``unique_indices`` (one update per call, and vmap keeps lanes
    on distinct rows).  Both matter: without them XLA CPU lowers the
    batched scatter to a copy-then-write, and the loop's per-step cost
    scales with J instead of staying O(1) — fatal for 10^5-job bags."""
    i = jnp.where(flag, idx, arr.shape[0])
    return arr.at[i].set(jnp.asarray(val, arr.dtype), mode="drop",
                         unique_indices=True)


def _init_state(B, J, V):
    """Batched initial carry: ``B`` independent lanes of zeroed sim state."""
    ft = jnp.result_type(float)
    it = jnp.int32

    def sc(v, dt):
        return jnp.full((B,), v, dt)

    return dict(
        now=sc(0.0, ft), seq=sc(0, it), cursor=sc(0, it),
        n_launch=sc(0, it), n_active=sc(0, it), n_done=sc(0, it),
        n_preempt=sc(0, it), n_fail=sc(0, it), n_defl=sc(0, it),
        n_rej=sc(0, it), n_events=sc(0, it), steps=sc(0, it),
        vm_hours=sc(0.0, ft), dollars=sc(0.0, ft),
        pending=sc(True, bool), halt=sc(False, bool),
        exhausted=sc(False, bool), rel_mode=sc(False, bool),
        stack=jnp.zeros((B, V), it), stack_len=sc(0, it),
        next_fresh=sc(0, it),
        alive=jnp.zeros((B, V), bool), launched=jnp.zeros((B, V), ft),
        life=jnp.zeros((B, V), ft), pre_at=jnp.full((B, V), np.inf, ft),
        seq_p=jnp.zeros((B, V), it),
        job=jnp.full((B, V), -1, it), fin_at=jnp.full((B, V), np.inf, ft),
        seq_f=jnp.zeros((B, V), it),
        has_exp=jnp.zeros((B, V), bool), exp_at=jnp.full((B, V), np.inf, ft),
        seq_e=jnp.zeros((B, V), it),
        ordv=jnp.zeros((B, V), it), cap=jnp.ones((B, V), ft),
        defl=jnp.zeros((B, V), bool), att_start=jnp.zeros((B, V), ft),
        att_w0=jnp.zeros((B, V), ft), att_done=jnp.zeros((B, V), ft),
        stack_done=jnp.zeros((B, V), ft),
        done=jnp.zeros((B, J), ft), fin_t=jnp.full((B, J), np.nan, ft),
        failures=jnp.zeros((B, J), it), attempts=jnp.zeros((B, J), it),
        rejected=jnp.zeros((B, J), bool),
    )


def _lane_step(lane, shared, s, *, n_slots: int):
    """ONE per-lane simulation step (unbatched; vmapped by the kernel)."""
    ft = jnp.result_type(float)
    it = jnp.int32
    lengths_all = shared["lengths"]     # (R, J)
    deadline_all = shared["deadlines"]  # (R, J)
    pool_all = shared["pools"]          # (Q, P)
    table_all = shared["tables"]        # (U, T, A) bool
    T_values = shared["T_values"]       # (T,)
    l_reuse = shared["reuse_L"]
    ro, hot = shared["relaunch_overhead"], shared["hot_spare_hours"]
    ckpt_on, ck_i, ck_c = (shared["ckpt_on"], shared["ckpt_interval"],
                           shared["ckpt_cost"])
    max_steps = shared["max_steps"]
    bidx, pidx, tidx = lane["bag_index"], lane["pool_index"], lane["table_index"]
    policy, cluster = lane["policy"], lane["cluster_size"]
    deflate_on, dfac = lane["deflate"], lane["deflate_factor"]
    price_row, price_dt = lane["price"], shared["price_dt"]

    V = n_slots
    J = lengths_all.shape[1]
    P = pool_all.shape[1]
    Tn = T_values.shape[0]
    A = table_all.shape[2]
    BIGI = jnp.asarray(_BIG, it)
    inf = jnp.asarray(np.inf, ft)
    zero = jnp.asarray(0.0, ft)
    slot_ids = jnp.arange(V, dtype=it)
    Tp = price_row.shape[0]

    def launch_price(launched):
        # the VM's locked-in spot price: its launch cell on the lane's
        # price row — the exact index arithmetic of the serial
        # ``BatchService.run``'s ``launch_price`` (floor == int-trunc for
        # launched >= 0, tail-clamped)
        k = jnp.clip(jnp.floor(launched / price_dt).astype(it), 0, Tp - 1)
        return price_row[k]

    # Each step function returns (scalar updates, per-array scatter deltas)
    # instead of a full next-state: every (V,)/(J,) array changes in at most
    # two slots per step, so the loop body's WRITES are O(1) and the branch
    # merge only touches scalars.  A naive ``jnp.where(pending, sa[k],
    # se[k])`` tree-merge would copy every array every iteration, making the
    # per-step cost scale with J — fatal for 10^5-job bags.

    def assign_step(s):
        """ONE iteration of the serial loop's greedy ``assign(t)``."""
        now, seqv = s["now"], s["seq"]
        q_empty = (s["stack_len"] == 0) & (s["next_fresh"] >= J)
        idle = s["alive"] & (s["job"] < 0)
        any_idle = jnp.any(idle)
        # release idle spares one per step, in vm_id (launch) order, so the
        # float accumulation into vm_hours happens in the serial order.
        # ``rel_mode`` mirrors the serial assign(t)'s entry check exactly:
        # spares are released only when the cascade STARTED with an empty
        # queue; a queue that empties mid-cascade leaves denied spares
        # alive until the next event's assign (they may yet be reused)
        rel = jnp.argmin(jnp.where(idle, s["ordv"], BIGI))
        rel_mode = s["rel_mode"]
        b_release = rel_mode & any_idle
        b_stop = (rel_mode & ~any_idle) | (~rel_mode & q_empty)

        top = jnp.maximum(s["stack_len"] - 1, 0)
        from_stack = s["stack_len"] > 0
        head = jnp.where(from_stack, s["stack"][top],
                         jnp.minimum(s["next_fresh"], J - 1))
        length_h = lengths_all[bidx, head]
        # the head's banked progress rides on the stack (pushed at preempt
        # time) instead of being gathered from the (J,) ``done`` array:
        # keeping ``done`` WRITE-ONLY inside the loop is what lets XLA
        # alias the (B, J) carry in place (a gather whose value feeds
        # another array's scatter forces a full per-step copy on CPU)
        done_h = jnp.where(from_stack, s["stack_done"][top], zero)
        rem = length_h - done_h
        n_ck = jnp.floor(rem / ck_i).astype(it).astype(ft)
        seg = jnp.where(ckpt_on, rem + n_ck * ck_c, rem)

        # model-policy approval: the exact index arithmetic of
        # engine.ReuseTable.decide, vectorized over the V candidate slots
        age = now - s["launched"]
        ti = jnp.searchsorted(T_values, rem).astype(it)
        t_lo = T_values[jnp.maximum(ti - 1, 0)]
        t_hi = T_values[jnp.minimum(ti, Tn - 1)]
        adj = (ti >= Tn) | ((ti > 0) & (rem - t_lo < t_hi - rem))
        ti = jnp.clip(ti - adj.astype(it), 0, Tn - 1)
        ai = jnp.clip(jnp.round(age / l_reuse * (A - 1)).astype(it), 0, A - 1)
        appr = jnp.where(policy == POLICY_MEMORYLESS, True,
                         table_all[tidx, ti, ai])
        approved = idle & appr
        any_appr = jnp.any(approved)
        cand = jnp.argmin(jnp.where(approved, s["ordv"], BIGI))

        can_launch = s["n_active"] < cluster
        free = jnp.argmin(jnp.where(s["alive"], BIGI, slot_ids))
        cap_c = s["cap"][cand]
        start_l = now + ro
        est_reuse = now + seg / cap_c
        est_launch = start_l + seg
        dl = deadline_all[bidx, head]
        rej_reuse = est_reuse > dl
        rej_launch = est_launch > dl

        b_reuse = ~q_empty & any_appr & ~rej_reuse
        b_rejct = ~q_empty & ((any_appr & rej_reuse) |
                              (~any_appr & can_launch & rej_launch))
        b_launch = ~q_empty & ~any_appr & can_launch & ~rej_launch
        b_block = ~q_empty & ~any_appr & ~can_launch
        pop = b_reuse | b_rejct | b_launch
        b_start = b_reuse | b_launch
        slot = jnp.where(b_reuse, cand, free)
        start_t = jnp.where(b_reuse, now, start_l)
        life_new = pool_all[pidx, jnp.minimum(s["cursor"], P - 1)]

        pop_stack = pop & (s["stack_len"] > 0)
        fin_val = jnp.where(b_reuse, now + seg / cap_c, start_l + seg)
        up = dict(
            now=now, halt=s["halt"], n_events=s["n_events"],
            n_preempt=s["n_preempt"], n_fail=s["n_fail"],
            n_defl=s["n_defl"], rel_mode=s["rel_mode"],
            vm_hours=s["vm_hours"] + jnp.where(
                b_release, now - s["launched"][rel], zero),
            # dollars mirrors every vm_hours increment: the same wall-clock
            # delta times the slot's launch-cell price (serial ``bill``)
            dollars=s["dollars"] + jnp.where(
                b_release,
                (now - s["launched"][rel]) * launch_price(s["launched"][rel]),
                zero),
            pending=~(b_stop | b_block),
            stack_len=s["stack_len"] - pop_stack.astype(it),
            next_fresh=s["next_fresh"] + (pop & ~pop_stack).astype(it),
            n_rej=s["n_rej"] + b_rejct.astype(it),
            n_done=s["n_done"] + b_rejct.astype(it),
            cursor=s["cursor"] + b_launch.astype(it),
            exhausted=s["exhausted"] | (b_launch & (s["cursor"] >= P)),
            n_launch=s["n_launch"] + b_launch.astype(it),
            n_active=(s["n_active"] + b_launch.astype(it)
                      - b_release.astype(it)),
            seq=seqv + jnp.where(b_launch, 2,
                                 jnp.where(b_reuse, 1, 0)).astype(it))
        deltas = dict(
            alive=[(rel, False, b_release), (free, True, b_launch)],
            rejected=[(head, True, b_rejct)],
            # fresh launch at now + relaunch_overhead
            launched=[(free, start_l, b_launch)],
            life=[(free, life_new, b_launch)],
            pre_at=[(free, start_l + life_new, b_launch)],
            seq_p=[(free, seqv, b_launch)],
            ordv=[(free, s["n_launch"], b_launch)],
            cap=[(free, jnp.asarray(1.0, ft), b_launch)],
            defl=[(free, False, b_launch)],
            # start the job (reused spare at now, fresh VM at start_l)
            job=[(slot, head, b_start)],
            att_start=[(slot, start_t, b_start)],
            att_w0=[(slot, zero, b_start)],
            att_done=[(slot, done_h, b_start)],
            fin_at=[(slot, fin_val, b_start)],
            seq_f=[(slot, jnp.where(b_reuse, seqv, seqv + 1), b_start)],
            has_exp=[(slot, False, b_start)],
            attempts=[(head, s["attempts"][head] + 1, b_start)])
        return up, deltas

    def event_step(s):
        """Advance to the next (time, seq)-minimal finish/preempt/expire."""
        times = jnp.stack([s["pre_at"], s["fin_at"], s["exp_at"]])
        valid = jnp.stack([s["alive"],
                           s["alive"] & (s["job"] >= 0),
                           s["alive"] & (s["job"] < 0) & s["has_exp"]])
        seqs = jnp.stack([s["seq_p"], s["seq_f"], s["seq_e"]])
        tt = jnp.where(valid, times, inf)
        t_min = jnp.min(tt)
        live = jnp.isfinite(t_min)
        sq = jnp.where(valid & (tt == t_min), seqs, BIGI)
        flat = jnp.argmin(sq.reshape(-1)).astype(it)
        kind = flat // V
        v = flat % V
        now = jnp.where(live, t_min, s["now"])
        j = s["job"][v]
        j0 = jnp.clip(j, 0, J - 1)

        k_pre = live & (kind == 0)
        k_fin = live & (kind == 1)
        k_exp = live & (kind == 2)
        defl_now = k_pre & deflate_on & (j >= 0) & ~s["defl"][v]
        kill = k_pre & ~defl_now
        # a slot with job >= 0 always holds an UNFINISHED job (finishing
        # clears vm.job in the same event), so j >= 0 alone decides this —
        # no fin_t read needed (keeping fin_t write-only in the loop lets
        # XLA alias it in place instead of copying (B, J) per step)
        job_running = kill & (j >= 0)

        dvh_kill = jnp.minimum(now - s["launched"][v], s["life"][v])
        dvh_exp = now - s["launched"][v]
        # checkpoint banking: whole (interval + cost) blocks of this
        # attempt's work-equivalent progress (serial: ran with capacity 1)
        ran = jnp.maximum(now - s["att_start"][v], zero)
        w = s["att_w0"][v] + ran * s["cap"][v]
        kck = jnp.floor(w / (ck_i + ck_c)).astype(it).astype(ft)
        len_j = lengths_all[bidx, j0]
        # banked progress comes from the slot's attempt snapshot, not from
        # a ``done`` gather (see the write-only note in assign_step)
        bank = jnp.minimum(s["att_done"][v] + kck * ck_i, len_j)
        sl = jnp.clip(s["stack_len"], 0, V - 1)
        stack_len = s["stack_len"] + job_running.astype(it)
        # deflation: survivor draws a fresh lifetime at the pool cursor
        life_new = pool_all[pidx, jnp.minimum(s["cursor"], P - 1)]
        w0 = s["att_w0"][v] + (now - s["att_start"][v]) * s["cap"][v]
        fin2 = now + (s["fin_at"][v] - now) * s["cap"][v] / dfac
        up = dict(
            now=now, halt=~live,
            pending=k_fin | kill | k_exp,
            n_events=s["n_events"] + live.astype(it),
            n_done=s["n_done"] + k_fin.astype(it),
            seq=s["seq"] + (k_fin | defl_now).astype(it),
            vm_hours=(s["vm_hours"] + jnp.where(kill, dvh_kill, zero)
                      + jnp.where(k_exp, dvh_exp, zero)),
            # kill and expire are mutually exclusive, so exactly one product
            # is billed (the other add is +0.0, exact on non-negative sums)
            dollars=(s["dollars"]
                     + jnp.where(kill,
                                 dvh_kill * launch_price(s["launched"][v]),
                                 zero)
                     + jnp.where(k_exp,
                                 dvh_exp * launch_price(s["launched"][v]),
                                 zero)),
            n_active=s["n_active"] - (kill | k_exp).astype(it),
            n_preempt=s["n_preempt"] + job_running.astype(it),
            n_fail=s["n_fail"] + job_running.astype(it),
            stack_len=stack_len,
            # the serial assign(now) releases idle spares only when ENTERED
            # with an empty queue — snapshot that entry condition per event
            rel_mode=(stack_len == 0) & (s["next_fresh"] >= J),
            cursor=s["cursor"] + defl_now.astype(it),
            exhausted=s["exhausted"] | (defl_now & (s["cursor"] >= P)),
            n_defl=s["n_defl"] + defl_now.astype(it),
            n_launch=s["n_launch"], n_rej=s["n_rej"],
            next_fresh=s["next_fresh"])
        deltas = dict(
            # finish: job completes, VM becomes a hot spare (k_fin and the
            # kill/ckpt-banking flags are mutually exclusive, so the merged
            # ``done`` write picks the branch by flag)
            fin_t=[(j0, now, k_fin)],
            done=[(j0, jnp.where(k_fin, len_j, bank),
                   k_fin | (job_running & ckpt_on))],
            job=[(v, -1, k_fin | kill)],
            exp_at=[(v, now + hot, k_fin)],
            seq_e=[(v, s["seq"], k_fin)],
            has_exp=[(v, k_fin, k_fin | k_exp)],
            # preempt (kill) / expire: slot dies, wall-clock is billed
            alive=[(v, False, kill | k_exp)],
            failures=[(j0, s["failures"][j0] + 1, job_running)],
            # preempted job goes to the FRONT of the queue (serial
            # ``queue.insert(0, .)``), carrying its done-work so the next
            # assign never reads the (J,) ``done`` array
            stack=[(sl, j0, job_running)],
            stack_done=[(sl, jnp.where(ckpt_on, bank, s["att_done"][v]),
                         job_running)],
            # deflation: capacity degrades, segment stretches, survivor
            # draws a fresh lifetime (one deflation per VM life)
            att_w0=[(v, w0, defl_now)],
            att_start=[(v, now, defl_now)],
            fin_at=[(v, fin2, defl_now)],
            cap=[(v, dfac, defl_now)],
            defl=[(v, True, defl_now)],
            pre_at=[(v, now + life_new, defl_now)],
            life=[(v, now + life_new - s["launched"][v], defl_now)],
            seq_p=[(v, s["seq"], defl_now)])
        return up, deltas

    # a lane that has finished (or halted / hit max_steps) freezes: its
    # scalar updates are where'd back to the old value and `active` is
    # AND-ed into every scatter mask, so the shared while_loop below can
    # keep iterating for the stragglers without touching done lanes
    active = (s["n_done"] < J) & ~s["halt"] & (s["steps"] < max_steps)
    sa, da = assign_step(s)
    se, de = event_step(s)
    p = s["pending"]
    out = dict(s)
    out.update({k: jnp.where(active, jnp.where(p, sa[k], se[k]), s[k])
                for k in sa})
    for k in set(da) | set(de):
        arr = s[k]
        for idx, val, flag in da.get(k, ()):
            arr = _setv(arr, idx, val, flag & p & active)
        for idx, val, flag in de.get(k, ()):
            arr = _setv(arr, idx, val, flag & ~p & active)
        out[k] = arr
    out["steps"] = s["steps"] + active.astype(it)
    return out


def _epilogue(s, price_row, price_dt, max_steps):
    """Per-lane exit accounting (vmapped over the final carry)."""
    ft = jnp.result_type(float)
    zero = jnp.asarray(0.0, ft)
    BIGI = jnp.asarray(_BIG, jnp.int32)
    V = s["alive"].shape[0]
    J = s["fin_t"].shape[0]
    Tp = price_row.shape[0]
    # bill still-running VMs in launch (vm_id) order so the sequential
    # float accumulation matches the serial epilogue exactly
    order = jnp.argsort(jnp.where(s["alive"], s["ordv"], BIGI))

    def acc(i, hd):
        h, d = hd
        v = order[i]
        alive = s["alive"][v]
        inc = s["now"] - s["launched"][v]
        k = jnp.clip(jnp.floor(s["launched"][v] / price_dt).astype(jnp.int32),
                     0, Tp - 1)
        return (h + jnp.where(alive, inc, zero),
                d + jnp.where(alive, inc * price_row[k], zero))

    vm_hours, dollars = jax.lax.fori_loop(0, V, acc,
                                          (s["vm_hours"], s["dollars"]))
    makespan = jnp.max(jnp.where(jnp.isnan(s["fin_t"]), s["now"],
                                 s["fin_t"]))
    return dict(
        makespan=makespan, vm_hours=vm_hours, dollars=dollars,
        final_time=s["now"],
        n_preemptions=s["n_preempt"], n_job_failures=s["n_fail"],
        n_deflations=s["n_defl"], n_rejected=s["n_rej"],
        n_launches=s["n_launch"], n_events=s["n_events"],
        steps=s["steps"], n_done=s["n_done"],
        pool_exhausted=s["exhausted"],
        deadlocked=s["halt"] & (s["n_done"] < J),
        truncated=(s["steps"] >= max_steps) & (s["n_done"] < J),
        finished_time=s["fin_t"], failures=s["failures"],
        attempts=s["attempts"], done_work=s["done"],
        rejected=s["rejected"])


@functools.partial(jax.jit, static_argnames=("n_slots",))
def _service_kernel(lane, shared, n_slots):
    # vmap the STEP, not the while_loop.  A vmapped ``lax.while_loop`` runs
    # until every lane's cond is false and re-selects EVERY carry leaf with
    # a full-array ``where(lane_active, new, old)`` each iteration — that
    # select copies the (B, J) job state per step, making the loop O(J) per
    # event.  One un-vmapped loop whose cond is ``any(lane active)`` and
    # whose body is the vmapped per-lane step (each lane gating its own
    # updates, see ``_lane_step``) has the same semantics but lets XLA
    # alias every carry buffer in place: per-step cost stays O(1) in J.
    B = lane["policy"].shape[0]
    J = shared["lengths"].shape[1]
    max_steps = shared["max_steps"]
    step = functools.partial(_lane_step, n_slots=n_slots)

    def body(s):
        return jax.vmap(step, in_axes=(0, None, 0))(lane, shared, s)

    def cond(s):
        return jnp.any((s["n_done"] < J) & ~s["halt"]
                       & (s["steps"] < max_steps))

    out = jax.lax.while_loop(cond, body, _init_state(B, J, n_slots))
    ep = functools.partial(_epilogue, max_steps=max_steps)
    return jax.vmap(ep, in_axes=(0, 0, None))(out, lane["price"],
                                              shared["price_dt"])


# ---------------------------------------------------------------------------
# public batched entry point
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ServiceBatchResult:
    """Per-lane outputs of one batched service dispatch (numpy, host-side)."""
    makespan: np.ndarray          # (B,)
    vm_hours: np.ndarray          # (B,)
    dollars: np.ndarray           # (B,) market-priced cost (== vm_hours when
    #                               run without price_rows: unit price rows)
    final_time: np.ndarray        # (B,) last processed event time
    n_preemptions: np.ndarray     # (B,)
    n_job_failures: np.ndarray    # (B,)
    n_deflations: np.ndarray      # (B,)
    n_rejected: np.ndarray        # (B,)
    n_launches: np.ndarray        # (B,)
    n_events: np.ndarray          # (B,) finish+preempt+expire events
    steps: np.ndarray             # (B,) while_loop iterations (incl. assigns)
    pool_exhausted: np.ndarray    # (B,) bool
    deadlocked: np.ndarray        # (B,) bool
    truncated: np.ndarray         # (B,) bool
    finished_time: np.ndarray     # (B, J) NaN = never finished
    failures: np.ndarray          # (B, J)
    attempts: np.ndarray          # (B, J)
    done_work: np.ndarray         # (B, J)
    rejected: np.ndarray          # (B, J) bool
    priced: bool = False          # True when real price_rows were supplied

    def __len__(self) -> int:
        return len(self.makespan)


def simulate_service_batch(
        *, lengths, pools, bag_index, pool_index, policy, cluster_size,
        tables=None, T_values=None, reuse_L: float = 1.0, table_index=None,
        deadlines=None, deflate=None, deflate_factor=0.5,
        checkpointing: bool = False, ckpt_interval: float = 0.5,
        ckpt_cost: float = 1.0 / 60.0,
        relaunch_overhead: float = RELAUNCH_OVERHEAD,
        hot_spare_hours: float = HOT_SPARE_HOURS,
        max_slots: Optional[int] = None, max_steps: Optional[int] = None,
        price_rows=None, price_dt: float = 1.0,
        on_exhausted: str = "raise") -> ServiceBatchResult:
    """Run B service lanes event-synchronously in ONE jitted dispatch.

    Deduplicated inputs (the PR-4 leading-axis convention): ``lengths`` is
    ``(R, J)`` unique bags, ``pools`` ``(Q, P)`` unique lifetime streams,
    ``tables`` ``(U, T, A)`` unique reuse-decision grids (from
    ``engine.ReuseTables.tables``; ``T_values``/``reuse_L`` are its shared
    remaining-work axis and deadline); per-lane ``bag_index`` /
    ``pool_index`` / ``table_index`` gather a lane's slice of each.

    ``policy`` is per-lane int codes (``POLICY_CODES``) or strings;
    ``deadlines`` an optional ``(R, J)`` per-job completion deadline (jobs
    whose estimated completion misses it are rejected at scheduling time);
    ``deflate``/``deflate_factor`` enable the per-lane VM-deflation branch.
    ``price_rows`` is an optional ``(B, Tp)`` (or broadcastable ``(Tp,)``)
    per-lane spot-price trace sampled every ``price_dt`` hours: each VM is
    billed for ALL its vm-hours at its launch-cell price (the serial
    ``BatchService(price_trace=...)`` convention), accumulating a per-lane
    ``dollars`` total bit-identical to the serial loop under x64 on shared
    pools.  Without ``price_rows`` the kernel bills unit prices, so
    ``dollars == vm_hours`` and ``priced`` is False.
    ``on_exhausted="raise"`` fails loudly when any lane consumes its whole
    lifetime pool or exceeds ``max_steps``; ``"flag"`` returns the per-lane
    flags instead.
    """
    lengths = np.atleast_2d(np.asarray(lengths, np.float64))
    pools = np.atleast_2d(np.asarray(pools, np.float64))
    if isinstance(policy, (str, int, np.integer)):
        policy = [policy]
    policy = np.asarray([POLICY_CODES[p] if isinstance(p, str) else int(p)
                         for p in np.atleast_1d(np.asarray(policy, object))],
                        np.int32)
    B = len(policy)
    bag_index = np.broadcast_to(np.asarray(bag_index, np.int32), (B,))
    pool_index = np.broadcast_to(np.asarray(pool_index, np.int32), (B,))
    cluster_size = np.broadcast_to(np.asarray(cluster_size, np.int32), (B,))
    if np.any(bag_index < 0) or np.any(bag_index >= len(lengths)):
        raise ValueError("bag_index out of range")
    if np.any(pool_index < 0) or np.any(pool_index >= len(pools)):
        raise ValueError("pool_index out of range")
    if np.any(cluster_size < 1):
        raise ValueError("cluster_size must be >= 1")
    if tables is None:
        if np.any(policy == POLICY_MODEL):
            raise ValueError("model-policy lanes need tables= (an "
                             "engine.ReuseTables tensor) and T_values=")
        tables = np.zeros((1, 1, 1), bool)
        T_values = np.zeros((1,), np.float64)
        table_index = np.zeros((B,), np.int32)
    else:
        tables = np.asarray(tables, bool)
        T_values = np.asarray(T_values, np.float64)
        if tables.ndim != 3 or tables.shape[1] != len(T_values):
            raise ValueError("tables must be (U, len(T_values), n_age)")
        table_index = (np.zeros((B,), np.int32) if table_index is None
                       else np.broadcast_to(
                           np.asarray(table_index, np.int32), (B,)))
        if np.any(table_index < 0) or np.any(table_index >= len(tables)):
            raise ValueError("table_index out of range")
    if deadlines is None:
        deadlines = np.full(lengths.shape, np.inf)
    else:
        deadlines = np.broadcast_to(
            np.asarray(deadlines, np.float64), lengths.shape)
    deflate = (np.zeros((B,), bool) if deflate is None
               else np.broadcast_to(np.asarray(deflate, bool), (B,)))
    dfac = np.broadcast_to(np.asarray(deflate_factor, np.float64), (B,))
    if np.any(deflate & ((dfac <= 0.0) | (dfac > 1.0))):
        raise ValueError("deflate_factor must be in (0, 1] on deflate lanes")
    if checkpointing and ckpt_interval <= 0:
        raise ValueError("ckpt_interval must be positive")
    priced = price_rows is not None
    if priced:
        price_rows = np.atleast_2d(np.asarray(price_rows, np.float64))
        if price_rows.shape[0] == 1:
            price_rows = np.broadcast_to(price_rows, (B, price_rows.shape[1]))
        if price_rows.shape[0] != B or price_rows.shape[1] == 0:
            raise ValueError("price_rows must be (B, Tp) or (Tp,)")
        if not np.all(price_rows > 0):
            raise ValueError("price_rows must be strictly positive")
        if not float(price_dt) > 0:
            raise ValueError("price_dt must be > 0")
    else:
        price_rows = np.ones((B, 1), np.float64)

    V = int(max_slots) if max_slots is not None else int(cluster_size.max())
    if V < int(cluster_size.max()):
        raise ValueError("max_slots must cover the largest cluster_size")
    J, P = lengths.shape[1], pools.shape[1]
    if max_steps is None:
        max_steps = 8 * (J + P) + 16 * V + 64

    ft = jnp.result_type(float)
    lane = dict(
        bag_index=jnp.asarray(bag_index), pool_index=jnp.asarray(pool_index),
        table_index=jnp.asarray(table_index), policy=jnp.asarray(policy),
        cluster_size=jnp.asarray(cluster_size), deflate=jnp.asarray(deflate),
        deflate_factor=jnp.asarray(dfac, ft),
        price=jnp.asarray(price_rows, ft))
    shared = dict(
        lengths=jnp.asarray(lengths, ft), deadlines=jnp.asarray(deadlines, ft),
        pools=jnp.asarray(pools, ft), tables=jnp.asarray(tables),
        T_values=jnp.asarray(T_values, ft),
        reuse_L=jnp.asarray(float(reuse_L), ft),
        relaunch_overhead=jnp.asarray(float(relaunch_overhead), ft),
        hot_spare_hours=jnp.asarray(float(hot_spare_hours), ft),
        ckpt_on=jnp.asarray(bool(checkpointing)),
        ckpt_interval=jnp.asarray(float(ckpt_interval), ft),
        ckpt_cost=jnp.asarray(float(ckpt_cost), ft),
        price_dt=jnp.asarray(float(price_dt), ft),
        max_steps=jnp.asarray(int(max_steps), jnp.int32))
    out = {k: np.asarray(v) for k, v in
           _service_kernel(lane, shared, V).items()}
    res = ServiceBatchResult(
        makespan=out["makespan"], vm_hours=out["vm_hours"],
        dollars=out["dollars"], priced=priced,
        final_time=out["final_time"], n_preemptions=out["n_preemptions"],
        n_job_failures=out["n_job_failures"], n_deflations=out["n_deflations"],
        n_rejected=out["n_rejected"], n_launches=out["n_launches"],
        n_events=out["n_events"], steps=out["steps"],
        pool_exhausted=out["pool_exhausted"], deadlocked=out["deadlocked"],
        truncated=out["truncated"], finished_time=out["finished_time"],
        failures=out["failures"], attempts=out["attempts"],
        done_work=out["done_work"], rejected=out["rejected"])
    if on_exhausted == "raise":
        if res.pool_exhausted.any():
            raise RuntimeError(
                f"service lifetime pool exhausted on lanes "
                f"{np.flatnonzero(res.pool_exhausted).tolist()}; increase "
                f"pool_size (P={P})")
        if res.truncated.any():
            raise RuntimeError(
                f"service kernel hit max_steps={max_steps} on lanes "
                f"{np.flatnonzero(res.truncated).tolist()}")
    elif on_exhausted != "flag":
        raise ValueError("on_exhausted must be 'raise' or 'flag'")
    return res


# ---------------------------------------------------------------------------
# grid-cell driver shared by service.run_bag_grid and scenarios.sweep_service
# ---------------------------------------------------------------------------

def run_cells_batched(*, cells: Sequence[dict], dists: Sequence,
                      lengths_by_seed: dict, reuse_tables=None,
                      pool_size: int = 4096, deadline_hours=None,
                      deflate_factor: float = 0.5,
                      checkpointing: bool = False, ckpt_interval: float = 0.5,
                      ckpt_cost: float = 1.0 / 60.0,
                      return_jobs: bool = False,
                      price_rows=None, price_dt: float = 1.0,
                      on_exhausted: str = "raise") -> list:
    """Run a list of grid cells through ONE batched kernel dispatch.

    Each cell is ``dict(dist_index, vm_type, policy, cluster_size, seed)``
    (policy may carry a ``"+deflate"`` suffix).  ``dists[dist_index]`` is
    the cell's lifetime model, ``lengths_by_seed[seed]`` its bag;
    ``reuse_tables`` an :class:`engine.ReuseTables` aligned with ``dists``
    (required iff any cell runs the model policy).  Lifetime pools are
    drawn once per unique ``(dist_index, seed)`` pair — the same per-seed
    reference streams the serial ``BatchService`` consumes, which is what
    makes serial-vs-batched comparisons bit-identical under x64.  Returns
    ``run_bag_grid``-style rows (cell coords + :class:`ServiceResult`).
    """
    cells = list(cells)
    if not cells:
        return []
    dists = list(dists)
    seeds_order = list(dict.fromkeys(c["seed"] for c in cells))
    bag_pos = {s: i for i, s in enumerate(seeds_order)}
    lengths = np.stack([np.asarray(lengths_by_seed[s], np.float64)
                        for s in seeds_order])
    pairs = list(dict.fromkeys((c["dist_index"], c["seed"]) for c in cells))
    pool_pos = {p: i for i, p in enumerate(pairs)}
    pool_mat = draw_service_pool_batch([dists[di] for di, _ in pairs],
                                       [s for _, s in pairs], size=pool_size)
    parsed = [split_policy(c["policy"]) for c in cells]
    tables = T_values = None
    reuse_L = 1.0
    if any(base == "model" for base, _ in parsed):
        if reuse_tables is None:
            raise ValueError("model-policy cells need reuse_tables=")
        tables, T_values = reuse_tables.tables, reuse_tables.T_values
        reuse_L = reuse_tables.L
    deadlines = (None if deadline_hours is None
                 else np.full(lengths.shape, float(deadline_hours)))
    res = simulate_service_batch(
        lengths=lengths, pools=pool_mat,
        bag_index=[bag_pos[c["seed"]] for c in cells],
        pool_index=[pool_pos[(c["dist_index"], c["seed"])] for c in cells],
        policy=[base for base, _ in parsed],
        cluster_size=[c["cluster_size"] for c in cells],
        tables=tables, T_values=T_values, reuse_L=reuse_L,
        table_index=[c["dist_index"] for c in cells],
        deadlines=deadlines, deflate=[d for _, d in parsed],
        deflate_factor=deflate_factor, checkpointing=checkpointing,
        ckpt_interval=ckpt_interval, ckpt_cost=ckpt_cost,
        price_rows=price_rows, price_dt=price_dt,
        on_exhausted=on_exhausted)
    rows = []
    for i, cell in enumerate(cells):
        bag = lengths[bag_pos[cell["seed"]]]
        rows.append(dict(vm_type=cell["vm_type"], policy=cell["policy"],
                         cluster_size=cell["cluster_size"], seed=cell["seed"],
                         result=lane_result(res, i, bag, cell["vm_type"],
                                            jobs=return_jobs)))
    return rows


def lane_result(res: ServiceBatchResult, i: int, bag_lengths, vm_type: str,
                *, jobs: bool = False) -> ServiceResult:
    """Package lane ``i`` as a serial-compatible :class:`ServiceResult`.

    The cost expressions mirror ``BatchService.run``'s epilogue exactly
    (same numpy float64 host arithmetic), so under x64 the whole row is
    bit-identical to the serial loop on a shared pool.  ``jobs=True``
    additionally materializes per-job :class:`Job` records (``started`` /
    ``attempt_started`` are not tracked by the kernel and stay ``None``).
    """
    vm_hours = float(res.vm_hours[i])
    price = PRICES_PREEMPTIBLE[vm_type]
    od_price = PRICES_ON_DEMAND[vm_type]
    cost = vm_hours * price
    # market dollars: the kernel's accumulated launch-cell billing when a
    # price trace was supplied, else the flat-price cost — the same
    # fallback as the serial epilogue
    dollars = float(res.dollars[i]) if res.priced else cost
    total_work = float(np.sum([float(l) for l in bag_lengths]))
    job_list = []
    if jobs:
        for j, l in enumerate(bag_lengths):
            fin = res.finished_time[i, j]
            job_list.append(Job(
                j, float(l), finished=None if np.isnan(fin) else float(fin),
                attempts=int(res.attempts[i, j]),
                failures=int(res.failures[i, j]),
                done_work=float(res.done_work[i, j])))
    return ServiceResult(
        makespan=float(res.makespan[i]), vm_hours=vm_hours,
        cost=cost, on_demand_cost=total_work * od_price,
        n_preemptions=int(res.n_preemptions[i]),
        n_job_failures=int(res.n_job_failures[i]), jobs=job_list,
        n_deflations=int(res.n_deflations[i]),
        n_rejected=int(res.n_rejected[i]), dollars=dollars)
