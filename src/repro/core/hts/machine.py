"""JAX cycle-accurate HTS machine (the paper's simulator as a compiled program).

Same semantics as :mod:`golden` (see its docstring for the within-cycle phase
order) but implemented with fixed-capacity state arrays and ``jax.lax``
control flow, so that

  * one simulation is a single ``jit``-compiled ``lax.while_loop``;
  * the per-class accelerator count ``n_fu`` is a *runtime argument*, so the
    Fig-10 strong-scaling sweep is one ``vmap`` over FU configurations;
  * an optional **event-skip** mode (beyond-paper) advances time directly to
    the next scheduler event instead of ticking every cycle — exact-equivalent
    schedules (tested), 10-400× faster wall-clock for interrupt-dominated
    (naive/software) cost models;
  * the scheduling policy (per-pid priority weights, per-class FU quotas and
    per-pid RS admission caps, ``policy.py``) enters as traced
    ``prio``/``quota``/``rs_cap`` arrays — like ``n_fu``, runtime arguments,
    so policy sweeps share one compilation;
  * the *program itself* is a runtime input (``ftab``/``p_len`` plus the
    ``mem_init``/``effects`` images), so a **population of scenarios** is one
    more ``vmap`` axis — ``batch.py`` packs N programs to a shared static
    shape and ``api.run_many`` drives them through one compiled machine.

GPR side effects on a squashed speculative path are rolled back from a
checkpoint taken at speculation entry (the paper is silent on GPR recovery;
an OoO core would checkpoint the RAT — DESIGN.md §3).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import isa
from .costs import FUNC_CYCLES, NUM_FUNCS, SchedulerCosts, norm_fu_cost
from .golden import HtsParams
from .policy import AGE_SPAN, NUM_PIDS, PRIO_CAP, SchedPolicy

I32 = jnp.int32
NEG = np.int32(-1)
BIG = np.int32(2**30)


def _named(name: str):
    """Decorator: the function's operations carry ``jax.named_scope(name)``
    in their metadata (``op_name``), so a profile names the step phase an
    operation belongs to.  Metadata only: the computation is unchanged."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


@dataclasses.dataclass(frozen=True)
class ResumableMachine:
    """The population machine factored into snapshot/resume pieces.

    ``init(ftab, p_len, n_fu, mem_init, effects, prio, quota, rs_cap,
    fu_cost, eft, streams)`` builds the while-loop carry (one state row
    per lane);
    ``run_slice(carry, <same 11 args>, budget)`` advances every alive lane
    by at most ``budget`` machine steps (while-loop trips — the unit wall
    time is spent in under event-skip) and returns the carry — lanes at
    their limit (or halted) are fixed points, so slices compose exactly:
    any split of a run into slices reaches the same final state as one
    uninterrupted run.  ``collect(carry)`` maps a carry (or one host-side
    row of it) to the usual output dict.

    ``budget`` is traced runtime data — varying it never recompiles — and
    the carry is an ordinary dict of arrays, so a host can snapshot it,
    harvest halted lanes, splice in freshly initialised rows (lane
    *refill*: only ``pc`` and ``mem`` of a fresh row depend on the
    program; see ``init``'s state layout) and resume.  That is the whole
    mechanism behind ``serve.py``'s slice-and-refill continuous batching.
    """
    init: Any
    run_slice: Any
    collect: Any


#: valid ``step_impl`` values — how the step body's scatter/select-heavy
#: phases are implemented (identical schedules, different lowering):
#: ``"xla"`` (default) is the restructured XLA form (cumsum ranks instead of
#: the argsort, one shared key-comparison matrix in the RS arbiter,
#: per-class unit ranking, fused trace selectors); ``"xla_base"`` preserves
#: the pre-restructure phase bodies verbatim (the honest benchmark
#: baseline); ``"pallas"`` runs the population step's hot phases as fused
#: ``pl.pallas_call`` kernels with a lane-per-program grid
#: (:mod:`pallas_step` — interpreted on CPU, real lowering on TPU).
STEP_IMPLS = ("xla", "xla_base", "pallas")


@dataclasses.dataclass(frozen=True)
class MachineSpec:
    """Static configuration baked into the compiled machine."""
    params: HtsParams = HtsParams()
    costs: SchedulerCosts = None
    max_fu_per_class: int = 16     # FU pool width (n_fu may be ≤ this, traced)
    event_skip: bool = True
    max_cycles: int = 5_000_000
    #: largest task-output dataframe (words) the completion datapath can
    #: write back in one cycle — a hardware write-port capacity.  Dispatching
    #: a task with a wider output raises the ``overflow`` flag (the
    #: simulation is refused, like a uid overflow).  Every Table-II bench
    #: and generated workload writes ≤ 8 words; the default matches the
    #: transactional-memory slot width (speculative outputs can never be
    #: wider than a TM slot anyway).
    max_out_words: int = 16
    #: step-body implementation (see :data:`STEP_IMPLS`).  Part of the
    #: compile key like every other field; the default value keeps the
    #: default path in the same compile bucket as before the field existed.
    step_impl: str = "xla"


def make_machine(spec: MachineSpec, max_prog: int = 256,
                 population: bool = False, resumable: bool = False,
                 step_impl: str | None = None):
    """Build the machine under ``spec``; returns
    ``run(ftab, p_len, n_fu, mem_init, effects, prio, quota, rs_cap,
    fu_cost, eft, streams)``.

    ``step_impl`` overrides ``spec.step_impl`` (see :data:`STEP_IMPLS`):
    ``"xla"`` / ``"xla_base"`` / ``"pallas"`` select how the hot step
    phases lower — all three produce bit-identical schedules (pinned by
    the differential tests).  The pallas implementation is population-
    level (``pl.pallas_call`` cannot sit under ``jax.vmap``), so a
    single-lane pallas machine runs as a population of one and squeezes
    the lane axis off its outputs — integer math, still bit-identical.

    With ``population=True`` the returned runner expects every argument
    with a leading *scenario* axis and simulates the whole batch in one
    while loop (scalar any-lane-alive condition, vmapped step body) — the
    fast path behind ``api.run_many``.  Unlike ``jax.vmap(run)``, it pays
    no per-lane select over the loop carry.

    With ``population=True, resumable=True`` the same machine comes back
    factored as a :class:`ResumableMachine` — the carry is built once
    (``init``), advanced in bounded step slices (``run_slice(carry, ...,
    budget)``; a lane at its per-lane step limit is a fixed point of the
    step, exactly like a halted lane, so slices compose bit-exactly with
    run-to-completion) and read out with ``collect``.  ``serve.py``
    builds continuous batching (harvest halted lanes between slices,
    refill their slots) on top of it.

    The *program is a runtime input* — ``ftab`` is the (max_prog, 10) decoded
    field table (``isa.decode_table`` output, zero-padded) and ``p_len`` its
    true length — so one compilation serves every benchmark, and ``vmap`` can
    batch over programs as well as FU configurations.

    ``n_fu``: (NUM_FUNCS,) int32 — units per accelerator class (traced).
    ``mem_init``/``effects``: (total_mem,) int32 images.
    ``prio``/``quota``/``rs_cap``: (NUM_PIDS,) int32 scheduling-policy tables
    (traced, like ``n_fu`` — one compilation serves every policy; see
    ``policy.py``).  ``prio`` holds per-pid priority weights (default
    all-zero = age order), ``quota`` per-pid in-flight unit caps per class
    (default uncapped), ``rs_cap`` per-pid RS-entry admission caps (default
    uncapped — a pid at its cap takes a structural dispatch stall exactly
    like a full RS).
    ``fu_cost``: (NUM_FUNCS, width) int32 per-(class, unit) execution-latency
    multipliers (traced; ``None`` = all ones — every unit of a class
    identical).  A width other than ``max_fu_per_class`` is sliced or
    1-padded to fit, so tables pack at the canonical ``costs.FU_COST_WIDTH``
    regardless of the machine's pool width.
    ``eft``: scalar int32 flag (traced) — nonzero selects earliest-finish-time
    unit ranking in the RS arbiter (``policy.issue_mode``); 0 is the
    historical greedy lowest-index rule, bit-identical.
    ``streams``: (n_streams, 4) int32 per-tenant frontend table —
    ``frontend.STREAM_FIELDS`` rows (start, end, arrival, weight); one
    per-stream program counter + decode window each, a frontend arbiter
    (round-robin, weight-class first) granting one eligible stream per
    cycle (see ``frontend.py`` and the golden docstring's phase 6).
    ``None`` = the historical single merged in-order frontend covering
    ``[0, p_len)`` — bit-identical to the pre-frontend machine.  The
    stream count is a *shape* (one compilation per stream count); the
    table's contents — boundaries, arrivals, weights — are traced runtime
    data, so arrival/weight sweeps never recompile.
    Returns a dict of schedule/trace arrays (see ``out`` at the bottom).

    Every argument is a runtime input, so ``vmap`` can batch any of three
    axes: the *scenario* axis (all arguments batched — a population of
    programs in one compiled machine), the *FU* axis (``n_fu`` alone) and
    the *policy* axis (``prio``/``quota``/``rs_cap``, with ``fu_cost``/
    ``eft`` riding the scenario axis); ``api.py`` composes them.
    """
    impl = spec.step_impl if step_impl is None else step_impl
    if impl not in STEP_IMPLS:
        raise ValueError(f"step_impl must be one of {STEP_IMPLS}, "
                         f"got {impl!r}")
    base = impl == "xla_base"
    p = spec.params
    c = spec.costs
    if p.max_tasks > AGE_SPAN:
        raise ValueError(
            f"max_tasks {p.max_tasks} exceeds the issue-key age span "
            f"{AGE_SPAN} (policy.AGE_SPAN); the int32 weighted-arbiter key "
            "would overflow")
    P = max_prog
    NF = NUM_FUNCS
    NFU = NF * spec.max_fu_per_class
    S = p.rs_entries
    T = p.tracker_entries
    L = p.tlb_entries
    M = p.total_mem
    U = p.max_tasks + 1            # uid-indexed trace arrays (uid 0 unused)
    C = p.cdb_entries or p.max_tasks   # CDB queue capacity (overflow-flagged)

    fu_cls = jnp.asarray(np.repeat(np.arange(NF), spec.max_fu_per_class), I32)
    fu_pos = jnp.asarray(np.tile(np.arange(spec.max_fu_per_class), NF), I32)
    func_cycles = jnp.asarray(FUNC_CYCLES, I32)
    mem_idx = jnp.arange(M, dtype=I32)
    # slot iotas: single-slot inserts are written as broadcast `where`
    # selects, not `.at[i].set` scatters — under the scenario vmap a
    # batched-index scatter lowers ~10x slower than a masked select
    s_iota = jnp.arange(S, dtype=I32)
    t_iota = jnp.arange(T, dtype=I32)
    l_iota = jnp.arange(L, dtype=I32)
    c_iota = jnp.arange(C, dtype=I32)
    u_iota = jnp.arange(U, dtype=I32)

    @_named("trace_write")
    def trace_write(arr, uid, value, enable):
        """``arr[uid] = value where enable`` for uid-indexed trace arrays.

        ``uid``/``enable`` may be scalars or aligned vectors (one slot per
        RS entry / FU).  The single machine writes through a scatter; the
        *base* population machine uses a one-hot select (its historical
        form — batched scatters were assumed to pay per *update × lane*).
        The restructured path scatters in the population machine too: a
        one-hot costs K×U compares per lane per trip whether or not any
        event fired, while the scatter costs only the handful of actual
        updates — measured even at serving-sized tables (U=65) and ~3×
        cheaper per trip at default capacities (U=1025), which is where
        the lane-width slope of the step body lived.  The per-lane pallas
        kernels made this obvious: inside a kernel body (no batch axis)
        the write is naturally a scatter (:func:`tw_scatter`).
        """
        uid = jnp.asarray(uid)
        if not population or not base:
            idx = jnp.where(enable, uid, U)
            return arr.at[idx].set(value, mode="drop")
        if uid.ndim == 0:
            hit = enable & (u_iota == uid)
        else:
            hit = (enable[:, None] & (uid[:, None] == u_iota[None, :])).any(0)
        return jnp.where(hit, value, arr)

    @_named("trace_write")
    def tw_scatter(arr, uid, value, enable):
        """The single-machine scatter form of :func:`trace_write`, used
        *inside* pallas kernels too: a kernel body runs per lane (no batch
        axis), so scatters are cheap again there even when the machine as
        a whole is a population."""
        uid = jnp.asarray(uid)
        idx = jnp.where(enable, uid, U)
        return arr.at[idx].set(value, mode="drop")

    # several trace arrays written under ONE (uid, enable) pair — e.g. the
    # frontend's four dispatch traces — share one selector instead of
    # recomputing it per array.  On the scatter path the selector is the
    # guarded index itself; the base population machine shares the
    # (U,)-wide one-hot (the dominant per-lane cost of its trace writes).
    @_named("trace_write")
    def trace_sel(uid, enable):
        if not population or not base:
            return jnp.where(enable, uid, U)
        return enable & (u_iota == uid)

    @_named("trace_write")
    def trace_put(arr, sel, value):
        if not population or not base:
            return arr.at[sel].set(value, mode="drop")
        return jnp.where(sel, value, arr)

    def init_state(mem_init, streams):
        # NB the read-only ``effects`` image is NOT part of the state: the
        # while-loop carry is select-masked per lane under batching, so
        # every loop-invariant array kept out of it is bandwidth saved on
        # every step of every scenario.
        z = functools.partial(jnp.zeros, dtype=I32)
        zb = functools.partial(jnp.zeros, dtype=jnp.bool_)
        NS = streams.shape[0]
        return dict(
            # per-stream frontends: a PC + decode window per tenant stream,
            # the arbiter's round-robin pointer, and per-stream
            # dispatch-stall counters (see frontend phase)
            pc=jnp.asarray(streams[:, 0], I32), cycle=I32(0), dt=I32(1),
            fe_wait=z(NS), fe_ptr=I32(0), fe_stall=z(NS),
            next_uid=I32(1), age=I32(0), ticket=I32(0),
            regs=z(p.num_regs), mem=jnp.asarray(mem_init, I32),
            rs_valid=zb(S), rs_uid=z(S), rs_func=z(S), rs_dep=z(S),
            rs_age=z(S), rs_out_s=z(S), rs_out_e=z(S), rs_src=z(S),
            rs_exec=z(S), rs_spec=zb(S), rs_pid=z(S),
            fu_busy=zb(NFU), fu_uid=z(NFU), fu_rem=z(NFU),
            fu_out_s=z(NFU), fu_out_e=z(NFU), fu_src=z(NFU), fu_spec=zb(NFU),
            fu_busy_cycles=z(NFU), fu_pid=z(NFU),
            trk_valid=zb(T), trk_s=z(T), trk_e=z(T), trk_uid=z(T), trk_spec=zb(T),
            tlb_valid=zb(L), tlb_os=z(L), tlb_oe=z(L), tlb_slot=z(L),
            tlb_seq=z(L), tlb_com=zb(L), tlb_seq_ctr=I32(0),
            cdb_valid=zb(C), cdb_uid=z(C), cdb_ticket=z(C), cdb_ready=z(C),
            cdb_spec=zb(C),
            br_active=jnp.bool_(False), br_kind=I32(0), br_pc=I32(0),
            br_off=I32(0), br_cond=I32(0), br_thr=I32(0), br_addr=I32(0),
            br_wait=I32(0), br_speculating=jnp.bool_(False),
            br_stream=I32(0),
            spec_active=jnp.bool_(False), spec_ckpt=z(p.num_regs),
            mr_active=jnp.bool_(False), mr_rem=I32(0),
            halted=jnp.bool_(False), overflow=jnp.bool_(False),
            stall_cycles=I32(0), spec_aborted=I32(0), steps=I32(0),
            mem_writes=I32(0),
            # uid-indexed trace
            tr_func=jnp.full((U,), NEG, I32), tr_dispatch=jnp.full((U,), NEG, I32),
            tr_issue=jnp.full((U,), NEG, I32), tr_complete=jnp.full((U,), NEG, I32),
            tr_broadcast=jnp.full((U,), NEG, I32), tr_dep=z(U),
            tr_aborted=zb(U), tr_pid=z(U),
        )

    # ------------------------------------------------------------------
    # shared helpers
    # ------------------------------------------------------------------
    def remap(st, addr):
        match = st["tlb_valid"] & (st["tlb_os"] <= addr) & (addr < st["tlb_oe"])
        seq = jnp.where(match, st["tlb_seq"], -1)
        best = jnp.argmax(seq)
        phys = (p.tm_base + st["tlb_slot"][best] * p.tm_slot_words
                + (addr - st["tlb_os"][best]))
        return jnp.where(match.any(), phys, addr)

    def tracker_lookup(st, s, e):
        ov = st["trk_valid"] & (st["trk_s"] < e) & (s < st["trk_e"])
        return jnp.max(jnp.where(ov, st["trk_uid"], 0))

    def eval_cond(cond, v, thr):
        return jnp.select(
            [cond == isa.CND_EQ, cond == isa.CND_NEQ, cond == isa.CND_GE],
            [v == thr, v != thr, v >= thr], v <= thr)

    def machine_empty(st):
        return (~st["rs_valid"].any() & ~st["fu_busy"].any()
                & ~st["cdb_valid"].any() & ~st["mr_active"] & ~st["br_active"])

    # ------------------------------------------------------------------
    # phase 1: FU tick (+ completion writes & CDB enqueue, FU-index order).
    # No per-unit conditional or full-memory masked copies — under the
    # scenario vmap a `lax.cond` becomes a select that runs every
    # iteration, and a per-unit loop of (total_mem,)-wide copies in the
    # hot body is what made population batches slower than a Python loop.
    # Memory effect-writes go through `copy_window` (a max_out_words-wide
    # dynamic-update-slice).  Under the vmap its per-lane slice and update
    # lower to a serial loop over lanes, so it runs once per window that is
    # actually written: a `while_loop` over the units that complete this
    # trip, in FU-index order (exact last-writer ordering), whose k-th
    # iteration picks the k-th completing unit from the completion ranks by
    # a masked reduction over the units (a per-lane gather would lower to
    # the same lane loop).  Batched, it runs the most completions of any
    # lane — usually none.  The CDB enqueue is vectorised with the same
    # ranks: the k-th completing unit (by FU index) takes the k-th free
    # slot (by slot index) and the k-th consecutive ticket, which is
    # precisely what the sequential argmin loop produced.  ``xla_base``
    # keeps the loop over every unit.
    # ------------------------------------------------------------------
    W = spec.max_out_words
    w_iota = jnp.arange(W, dtype=I32)

    def copy_window(dst_arr, src_arr, dst, src, n, enable):
        """``dst_arr[dst:dst+n] = src_arr[src:src+n]`` via one W-wide DUS.

        Exactly a masked full-memory range copy for ``n <= W`` (the
        dispatch guard enforces that), at window cost instead of
        (total_mem,) cost per call.
        """
        dst_c = jnp.clip(dst, 0, M - W)
        off = dst - dst_c
        cur = jax.lax.dynamic_slice(dst_arr, (dst_c,), (W,))
        vals = src_arr[jnp.clip(w_iota - off + src, 0, M - 1)]
        mask = enable & (w_iota >= off) & (w_iota < off + n)
        return jax.lax.dynamic_update_slice(dst_arr,
                                            jnp.where(mask, vals, cur),
                                            (dst_c,))

    def copy_each(mem, count, window):
        """``mem = window(k, mem)`` for k in ``range(count)``: a while loop,
        so batched it runs the largest ``count`` of any lane, and no window
        is copied on a trip where no lane writes."""
        return jax.lax.while_loop(
            lambda c: c[0] < count,
            lambda c: (c[0] + 1, window(c[0], c[1])), (I32(0), mem))[1]

    @_named("fu_exec")
    def fu_exec(st, exists, effect, alive):
        """Per-unit execution tick + completion memory writes (one window
        copy per completing unit, counted in ``mem_writes``); returns the
        ``done`` mask for the slot-side CDB enqueue (its own phase so the
        pallas machine can vmap this half and kernel the enqueue)."""
        busy = st["fu_busy"] & exists & alive
        st["fu_busy_cycles"] = st["fu_busy_cycles"] + jnp.where(busy, st["dt"], 0)
        rem = jnp.where(busy, st["fu_rem"] - st["dt"], st["fu_rem"])
        done = busy & (rem <= 0)
        st["fu_rem"] = rem

        # --- memory writes (FU-index order: later units overwrite)
        n_done = jnp.sum(done, dtype=I32)
        st["mem_writes"] = st["mem_writes"] + n_done
        if base:
            def mem_trip(i, mem):
                return copy_window(mem, effect, st["fu_out_s"][i],
                                   st["fu_src"][i],
                                   st["fu_out_e"][i] - st["fu_out_s"][i],
                                   done[i])
            st["mem"] = jax.lax.fori_loop(0, NFU, mem_trip, st["mem"])
            return st, done

        csum = jnp.cumsum(done.astype(I32))

        def write_kth(k, mem):
            hit = done & (csum == k + 1)       # the k-th completing unit

            def pick(a):
                return jnp.sum(jnp.where(hit, a, 0), dtype=I32)
            s, e = pick(st["fu_out_s"]), pick(st["fu_out_e"])
            return copy_window(mem, effect, s, pick(st["fu_src"]), e - s,
                               True)
        st["mem"] = copy_each(st["mem"], n_done, write_kth)
        return st, done

    @_named("cdb_enqueue")
    def cdb_enqueue(st, done, tw):
        # --- CDB enqueue: k-th done unit → k-th free slot, ticket + k.
        # Written slot-side ((C,)-wide selects + gathers, no scatters —
        # batched scatters pay per update) — identical to the sequential
        # argmin loop: the slot of free-rank r receives the done unit of
        # FU-index-rank r and the r-th consecutive ticket.
        n_done = jnp.sum(done, dtype=I32)
        free = ~st["cdb_valid"]
        free_rank = jnp.cumsum(free.astype(I32)) - 1              # slot rank
        n_free = jnp.sum(free, dtype=I32)
        n_enq = jnp.minimum(n_done, n_free)
        fr = jnp.clip(free_rank, 0, NFU - 1)
        if base:
            # unit_of_rank[r]: the r-th completing unit in FU-index order
            k = jnp.cumsum(done.astype(I32)) - 1                  # unit rank
            unit_of_rank = jnp.argsort(jnp.where(done, k, BIG)).astype(I32)
            u = unit_of_rank[fr]                                  # (C,)
        else:
            # same rank → unit map without the (NFU,)-argsort: csum[i]
            # counts completions through unit i, so the first index with
            # csum ≥ r+1 IS the r-th completing unit — a log2(NFU) binary
            # search per slot.  Slots past n_enq are masked by ``take``.
            csum = jnp.cumsum(done.astype(I32))
            u = jnp.clip(jnp.searchsorted(csum, fr + 1, side="left"),
                         0, NFU - 1).astype(I32)
        take = free & (free_rank < n_enq)
        st["cdb_valid"] = st["cdb_valid"] | take
        st["cdb_uid"] = jnp.where(take, st["fu_uid"][u], st["cdb_uid"])
        st["cdb_ticket"] = jnp.where(take, st["ticket"] + free_rank,
                                     st["cdb_ticket"])
        st["cdb_ready"] = jnp.where(take, st["cycle"] + c.completion_extra,
                                    st["cdb_ready"])
        st["cdb_spec"] = jnp.where(take, st["fu_spec"][u], st["cdb_spec"])
        st["ticket"] = st["ticket"] + n_enq
        st["overflow"] = st["overflow"] | (n_done > n_free)

        # --- trace + unit release
        st["tr_complete"] = tw(st["tr_complete"], st["fu_uid"],
                               st["cycle"], done)
        st["fu_busy"] = st["fu_busy"] & ~done
        st["fu_uid"] = jnp.where(done, 0, st["fu_uid"])
        return st

    # ------------------------------------------------------------------
    # phase 2+3: memread tick and CDB grant
    # ------------------------------------------------------------------
    @_named("memread_tick")
    def memread_tick(st, alive):
        ticking = st["mr_active"] & alive
        rem = jnp.where(ticking, st["mr_rem"] - st["dt"], st["mr_rem"])
        fired = ticking & (rem <= 0)
        st["mr_rem"] = rem
        st["mr_active"] = st["mr_active"] & ~fired
        return st, fired

    @_named("cdb_grant")
    def cdb_grant(st, br_ready, alive, tw, unroll=False):
        def grant_one(st, br_ready):
            ready = st["cdb_valid"] & (st["cdb_ready"] <= st["cycle"]) & alive
            idx = jnp.argmin(jnp.where(ready, st["cdb_ticket"], BIG))
            has = ready.any()
            uid = st["cdb_uid"][idx]
            st["cdb_valid"] = st["cdb_valid"] & ~(has & (c_iota == idx))
            st["rs_dep"] = jnp.where(has & (st["rs_dep"] == uid), 0, st["rs_dep"])
            st["trk_valid"] = st["trk_valid"] & ~(has & (st["trk_uid"] == uid))
            st["tr_broadcast"] = tw(st["tr_broadcast"], uid,
                                    st["cycle"], has)
            br_ready = br_ready | (has & st["br_active"]
                                   & (st["br_kind"] == isa.BR_BR)
                                   & (st["br_wait"] == uid))
            return st, br_ready
        # every scheduler model grants one broadcast per cycle (cdb_width
        # 1), so the restructured path inlines the single grant instead of
        # paying a length-1 ``lax.scan``; kernels unroll wider widths too
        # (a Python loop of the same body — identical ops, no scan carry)
        if (not base and c.cdb_width == 1) or unroll:
            for _ in range(c.cdb_width):
                st, br_ready = grant_one(st, br_ready)
            return st, br_ready

        def body(carry, _):
            return grant_one(*carry), None
        (st, br_ready), _ = jax.lax.scan(body, (st, br_ready), None,
                                         length=c.cdb_width)
        return st, br_ready

    # ------------------------------------------------------------------
    # phase 4: branch resolution
    # ------------------------------------------------------------------
    @_named("branch_core")
    def branch_core(st, br_ready):
        """Branch resolution minus the two ``tr_aborted`` trace writes —
        returns the kill masks plus the uid arrays *as of the squash* (the
        core zeroes ``fu_uid``, and the frontend later overwrites
        ``rs_uid`` slots) so the caller can apply the aborted traces in
        whichever form suits its backend."""
        fire = st["br_active"] & br_ready
        value = st["mem"][remap(st, st["br_addr"])]
        taken = eval_cond(st["br_cond"], value, st["br_thr"])
        target = st["br_pc"] + jnp.where(taken, st["br_off"], 1)
        spec = st["br_speculating"]

        commit = fire & spec & ~taken
        squash = fire & spec & taken
        plain = fire & ~spec

        # --- commit: speculative state becomes architectural
        st["tlb_com"] = st["tlb_com"] | (commit & st["tlb_valid"])
        st["trk_spec"] = st["trk_spec"] & ~commit
        st["rs_spec"] = st["rs_spec"] & ~commit
        st["fu_spec"] = st["fu_spec"] & ~commit
        st["cdb_spec"] = st["cdb_spec"] & ~commit

        # --- squash: discard speculative state, roll back, redirect
        rs_kill = squash & st["rs_valid"] & st["rs_spec"]
        fu_kill = squash & st["fu_busy"] & st["fu_spec"]
        rs_uid_k, fu_uid_k = st["rs_uid"], st["fu_uid"]
        st["spec_aborted"] = (st["spec_aborted"]
                              + rs_kill.sum(dtype=I32) + fu_kill.sum(dtype=I32))
        st["rs_valid"] = st["rs_valid"] & ~rs_kill
        st["fu_busy"] = st["fu_busy"] & ~fu_kill
        st["fu_uid"] = jnp.where(fu_kill, 0, st["fu_uid"])
        st["trk_valid"] = st["trk_valid"] & ~(squash & st["trk_spec"])
        st["tlb_valid"] = st["tlb_valid"] & ~(squash & ~st["tlb_com"])
        st["cdb_valid"] = st["cdb_valid"] & ~(squash & st["cdb_spec"])
        st["regs"] = jnp.where(squash, st["spec_ckpt"], st["regs"])
        # the redirect (and the squash's decode-window reset) lands on the
        # branch-owning stream only
        mine = jnp.arange(st["pc"].shape[0], dtype=I32) == st["br_stream"]
        st["pc"] = jnp.where((squash | plain) & mine, target, st["pc"])
        st["fe_wait"] = jnp.where(squash & mine, 0, st["fe_wait"])

        st["spec_active"] = st["spec_active"] & ~(commit | squash)
        st["br_active"] = st["br_active"] & ~fire
        return st, (rs_uid_k, rs_kill, fu_uid_k, fu_kill)

    @_named("abort_traces")
    def abort_traces(st, kills, tw):
        rs_uid_k, rs_kill, fu_uid_k, fu_kill = kills
        st["tr_aborted"] = tw(st["tr_aborted"], rs_uid_k, True, rs_kill)
        st["tr_aborted"] = tw(st["tr_aborted"], fu_uid_k, True, fu_kill)
        return st

    # ------------------------------------------------------------------
    # phase 5: RS issue — weighted arbiter.  Ready entries are ordered by
    # the policy's scalar issue key (priority class first, age within a
    # class; all-equal weights degrade to pure age order).  A pid at its
    # per-class in-flight quota is masked out of the per-class free-rank
    # computation without consuming the unit, so the arbiter stays
    # work-conserving.  ``prio``/``quota`` are traced runtime arrays
    # (like ``n_fu``), so policies sweep under vmap without recompiling.
    # Unit selection within a class is a ranking too: free units are
    # ordered by ``ckey`` — plain FU index under greedy, (cost, index)
    # under EFT (``eft`` traced flag).  A granted entry's predicted
    # finish on a *free* unit is base cycles × unit cost (the busy
    # horizon of a free unit is zero, and busy units are never granted),
    # and the base is constant per class, so cost order IS finish order
    # for every entry — the k-th fired entry taking the k-th ckey-ranked
    # unit reproduces the golden oracle's sequential earliest-finish
    # pick exactly.  With eft=0 ckey is the FU index and the arbiter is
    # bit-identical to the historical greedy one.
    # ------------------------------------------------------------------
    nfu_iota = jnp.arange(NFU, dtype=I32)

    def _issue_apply(st, m, fire, cost, tw):
        """Shared arbiter tail: apply the entry→unit match matrix."""
        entry_of_unit = jnp.argmax(m, axis=0)      # valid where any col
        unit_hit = m.any(axis=0)

        st["fu_busy"] = st["fu_busy"] | unit_hit
        st["fu_uid"] = jnp.where(unit_hit, st["rs_uid"][entry_of_unit], st["fu_uid"])
        st["fu_rem"] = jnp.where(unit_hit,
                                 st["rs_exec"][entry_of_unit] * cost,
                                 st["fu_rem"])
        st["fu_out_s"] = jnp.where(unit_hit, st["rs_out_s"][entry_of_unit],
                                   st["fu_out_s"])
        st["fu_out_e"] = jnp.where(unit_hit, st["rs_out_e"][entry_of_unit],
                                   st["fu_out_e"])
        st["fu_src"] = jnp.where(unit_hit, st["rs_src"][entry_of_unit], st["fu_src"])
        st["fu_spec"] = jnp.where(unit_hit, st["rs_spec"][entry_of_unit],
                                  st["fu_spec"])
        st["fu_pid"] = jnp.where(unit_hit, st["rs_pid"][entry_of_unit],
                                 st["fu_pid"])
        st["tr_issue"] = tw(st["tr_issue"], st["rs_uid"],
                            st["cycle"], fire)
        st["rs_valid"] = st["rs_valid"] & ~fire
        return st

    def rs_issue_base(st, exists, prio, quota, cost, eft, alive, tw):
        ready = st["rs_valid"] & (st["rs_dep"] == 0) & alive
        free = exists & ~st["fu_busy"]
        n_free = jnp.zeros((NF,), I32).at[fu_cls].add(free.astype(I32))
        w = jnp.clip(prio[st["rs_pid"]], 0, PRIO_CAP)
        key = jnp.where(ready, (PRIO_CAP - w) * AGE_SPAN + st["rs_age"], BIG)
        key_lt = key[None, :] < key[:, None]
        same_cls = st["rs_func"][:, None] == st["rs_func"][None, :]
        same_pid = st["rs_pid"][:, None] == st["rs_pid"][None, :]
        # quota mask: units already running for (pid, class) plus ready
        # same-(pid, class) entries ahead in key order must stay under cap.
        # (An ahead entry that fails to issue can only fail for a resource
        # — class units or issue width — that equally blocks this entry,
        # so counting candidates instead of winners is exact.)
        busy = st["fu_busy"] & exists
        inflight = ((busy[None, :]
                     & (st["fu_pid"][None, :] == st["rs_pid"][:, None])
                     & (fu_cls[None, :] == st["rs_func"][:, None]))
                    .sum(axis=1).astype(I32))
        q_ahead = key_lt & same_cls & same_pid & ready[None, :]
        q_rank = q_ahead.sum(axis=1).astype(I32)
        quota_ok = inflight + q_rank < quota[st["rs_pid"]]
        eligible = ready & quota_ok
        # rank among eligible entries of the same class, by key
        c_ahead = key_lt & same_cls & eligible[None, :]
        cls_rank = c_ahead.sum(axis=1).astype(I32)
        issuable = eligible & (cls_rank < n_free[st["rs_func"]])
        # global width cap: smallest keys among issuable
        g_key = jnp.where(issuable, key, BIG)
        g_rank = (g_key[None, :] < g_key[:, None]).sum(axis=1).astype(I32)
        fire = issuable & (g_rank < c.issue_width)
        # among fired entries of a class, k-th by key → k-th free unit by index
        f_key = jnp.where(fire, key, BIG)
        f_ahead = (f_key[None, :] < f_key[:, None]) & same_cls & fire[None, :]
        f_rank = f_ahead.sum(axis=1).astype(I32)
        # per-class free rank: rank among free units of same class, by
        # ckey (greedy: FU index; eft: cost-major, index-minor — ckey is
        # unique per unit, so the ranking is a strict total order)
        ckey = (jnp.where(eft != 0, cost, 0) * NFU
                + jnp.arange(NFU, dtype=I32))
        cls_eq = fu_cls[None, :] == fu_cls[:, None]
        lower = cls_eq & free[None, :] & (ckey[None, :] < ckey[:, None])
        unit_rank = lower.sum(axis=1).astype(I32)
        # match matrix: entry e → unit u
        m = (fire[:, None] & free[None, :]
             & (st["rs_func"][:, None] == fu_cls[None, :])
             & (f_rank[:, None] == unit_rank[None, :]))
        return _issue_apply(st, m, fire, cost, tw)

    def rs_issue_fast(st, exists, prio, quota, cost, eft, alive, tw):
        """The restructured arbiter: same selection function as
        :func:`rs_issue_base` (bit-identical by the differential tests),
        restructured for the population width-cost curve — ONE (S, S) key
        comparison matrix feeds every rank (the issue key is unique among
        ready entries, so masking columns of ``key_lt`` IS re-ranking the
        masked subset), the unit ranking collapses from (NFU, NFU) to a
        per-class (NF, W, W) block, and rank sums narrow to int16 (S ≤ 32
        entries, ≤ W ≤ 16 units per class — int16 is exact)."""
        I16 = jnp.int16
        ready = st["rs_valid"] & (st["rs_dep"] == 0) & alive
        free = exists & ~st["fu_busy"]
        n_free = jnp.zeros((NF,), I32).at[fu_cls].add(free.astype(I32))
        w = jnp.clip(prio[st["rs_pid"]], 0, PRIO_CAP)
        key = jnp.where(ready, (PRIO_CAP - w) * AGE_SPAN + st["rs_age"], BIG)
        key_lt = key[None, :] < key[:, None]
        same_cls = st["rs_func"][:, None] == st["rs_func"][None, :]
        same_pid = st["rs_pid"][:, None] == st["rs_pid"][None, :]
        busy = st["fu_busy"] & exists
        inflight = ((busy[None, :]
                     & (st["fu_pid"][None, :] == st["rs_pid"][:, None])
                     & (fu_cls[None, :] == st["rs_func"][:, None]))
                    .sum(axis=1, dtype=I16))
        q_rank = (key_lt & same_cls & same_pid
                  & ready[None, :]).sum(axis=1, dtype=I16)
        quota_ok = inflight + q_rank < quota[st["rs_pid"]]
        eligible = ready & quota_ok
        cls_rank = (key_lt & same_cls & eligible[None, :]).sum(axis=1,
                                                               dtype=I16)
        issuable = eligible & (cls_rank < n_free[st["rs_func"]])
        # ``key`` is BIG on every non-ready entry and unique among ready
        # ones, so "rank within subset X" is just key_lt with X's columns
        # — no per-subset masked key or fresh comparison matrix needed
        g_rank = (key_lt & issuable[None, :]).sum(axis=1, dtype=I16)
        fire = issuable & (g_rank < c.issue_width)
        f_rank = (key_lt & same_cls & fire[None, :]).sum(axis=1, dtype=I16)
        # per-class unit ranking: units only ever compare within their own
        # class, so the (NFU, NFU) cls_eq matrix is 1/NF dead weight —
        # rank inside (NF, W, W) blocks instead
        Wc = spec.max_fu_per_class
        ckey = (jnp.where(eft != 0, cost, 0) * NFU
                + nfu_iota).reshape(NF, Wc)
        free_c = free.reshape(NF, Wc)
        lower = free_c[:, None, :] & (ckey[:, None, :] < ckey[:, :, None])
        unit_rank = lower.sum(axis=2, dtype=I16).reshape(NFU)
        m = (fire[:, None] & free[None, :]
             & (st["rs_func"][:, None] == fu_cls[None, :])
             & (f_rank[:, None] == unit_rank[None, :]))
        return _issue_apply(st, m, fire, cost, tw)

    rs_issue = _named("rs_issue")(rs_issue_base if base else rs_issue_fast)

    # ------------------------------------------------------------------
    # phase 6: frontend — N per-tenant streams, one arbitrated dispatch.
    # Eligibility is computed per stream (arrived, undrained, decode
    # window free, not stalled on its own branch, next instruction able
    # to act), then one stream is granted by the arbiter key: frontend
    # weight class first, round-robin within a class.  A structurally
    # stalled TASK (full RS / tracker / pid at its rs_cap) makes its
    # stream ineligible — the arbiter skips it, which is what turns RS
    # admission caps into per-stream backpressure instead of the merged
    # model's head-of-line stall.  A single stream covering [0, p_len)
    # reduces to the historical merged frontend bit-for-bit.
    # ------------------------------------------------------------------
    @_named("frontend_core")
    def frontend_core(st, F, p_len, rs_cap, streams, alive):
        NS = streams.shape[0]
        ns_iota = jnp.arange(NS, dtype=I32)
        s_start, s_end = streams[:, 0], streams[:, 1]
        s_arr = streams[:, 2]
        s_w = jnp.clip(streams[:, 3], 0, PRIO_CAP)
        s_active = s_end > s_start
        pcs = st["pc"]
        drained_pre = pcs >= s_end
        arrived = st["cycle"] >= s_arr
        fe_free = st["fe_wait"] == 0

        # one shared branch unit / speculation domain: while a speculation
        # is open only the speculating stream runs; a non-speculative
        # branch stalls only its own stream
        br_mine = ns_iota == st["br_stream"]
        br_ok = jnp.where(st["br_active"],
                          jnp.where(st["br_speculating"], br_mine, ~br_mine),
                          True)
        base_elig = s_active & arrived & ~drained_pre & fe_free & br_ok & alive

        pccs = jnp.clip(pcs, 0, max(P - 1, 0))
        ops_s = F["op"][pccs]
        pids_s = F["pid"][pccs]
        kinds_s = F["ctl"][pccs] & 0x3

        # TASK-instruction gates (structural stalls + speculative TLB/TM)
        rs_full = st["rs_valid"].all()
        trk_full = st["trk_valid"].all()
        rs_of_pid = (st["rs_valid"][None, :]
                     & (st["rs_pid"][None, :] == pids_s[:, None])
                     ).sum(axis=1).astype(I32)
        pid_capped_s = rs_of_pid >= rs_cap[pids_s]
        empty_req = jnp.bool_(c.in_order) & ~machine_empty(st)
        spec = st["spec_active"]
        slot_used = jax.vmap(
            lambda s: (st["tlb_valid"] & (st["tlb_slot"] == s)).any())(
                jnp.arange(p.tm_slots))
        tm_slot = jnp.argmin(slot_used)
        tm_avail = ~slot_used.all()
        tlb_full = st["tlb_valid"].all()
        committed_seq = jnp.where(st["tlb_valid"] & st["tlb_com"],
                                  st["tlb_seq"], BIG)
        victim = jnp.argmin(committed_seq)
        has_victim = (committed_seq[victim] < BIG)
        # under speculation a TASK can act iff it can take a TLB/TM slot,
        # or a committed victim can be drained to free one
        spec_gate = jnp.where(tm_avail, ~tlb_full, has_victim)
        task_ok = (~rs_full & ~trk_full & ~pid_capped_s & ~empty_req
                   & (~spec | spec_gate))
        # IF: the one branch unit must be free; MR/BR additionally respect
        # the in-order cost model's empty-machine requirement
        if_ok = ~st["br_active"] & ((kinds_s == isa.BR_RR) | ~empty_req)
        elig = base_elig & jnp.where(ops_s == isa.OP_TASK, task_ok,
                                     jnp.where(ops_s == isa.OP_IF, if_ok,
                                               True))

        # the arbiter: weight class first, round-robin within a class
        key = jnp.where(elig, (PRIO_CAP - s_w) * NS
                        + ((ns_iota - st["fe_ptr"]) % NS), BIG)
        gidx = jnp.argmin(key).astype(I32)
        has = elig.any()
        gmask = has & (ns_iota == gidx)
        st["fe_ptr"] = jnp.where(has, (gidx + 1) % NS, st["fe_ptr"])

        # decode windows tick every cycle on every stream
        st["fe_wait"] = jnp.where(alive,
                                  jnp.maximum(st["fe_wait"] - st["dt"], 0),
                                  st["fe_wait"])

        # dispatch-stall accounting for this cycle (the event-skipped
        # window behind it is charged at the top of ``step`` — from
        # pre-phase state, before a branch squash can redirect a pc)
        stalled_now = s_active & arrived & ~drained_pre & ~gmask
        st["fe_stall"] = st["fe_stall"] + jnp.where(
            alive, stalled_now.astype(I32), 0)

        # scalar fetch of the granted stream's instruction
        pcc = pccs[gidx]
        pc_g = pcs[gidx]
        op = ops_s[gidx]
        a, asz, b, bsz = F["a"][pcc], F["asz"][pcc], F["b"][pcc], F["bsz"][pcc]
        ctl = F["ctl"][pcc]
        acc = F["acc"][pcc]
        active = has

        progressed = jnp.bool_(False)

        # ---- control ops (1 cycle each) --------------------------------
        is_add = active & (op == isa.OP_ADD)
        is_mul = active & (op == isa.OP_MUL)
        is_mov = active & (op == isa.OP_MOV)
        is_jmp = active & (op == isa.OP_JUMP)
        is_lbeg = active & (op == isa.OP_LBEG)
        is_lend = active & (op == isa.OP_LEND)
        is_nop = active & (op == isa.OP_NOP)

        regs = st["regs"]
        val = jnp.select(
            [is_add, is_mul, is_mov, is_lbeg],
            [regs[a] + regs[asz], regs[a] * regs[asz],
             jnp.where(ctl & isa.CTL_IMM, a, regs[a]),
             jnp.where(ctl & 1, regs[a], a)],
            0)
        wr_reg = jnp.select([is_add | is_mul | is_mov, is_lbeg],
                            [b, asz], -1)
        lend_val = regs[asz] - 1
        regs = jnp.where((jnp.arange(p.num_regs) == wr_reg)
                         & (is_add | is_mul | is_mov | is_lbeg), val, regs)
        regs = jnp.where((jnp.arange(p.num_regs) == asz) & is_lend,
                         lend_val, regs)
        st["regs"] = regs

        pc_next = pc_g
        pc_next = jnp.where(is_add | is_mul | is_mov | is_lbeg | is_nop,
                            pc_g + 1, pc_next)
        pc_next = jnp.where(is_jmp, a, pc_next)
        pc_next = jnp.where(is_lend,
                            jnp.where(lend_val > 0, pc_g - b, pc_g + 1),
                            pc_next)
        progressed = progressed | is_add | is_mul | is_mov | is_jmp \
            | is_lbeg | is_lend | is_nop

        # ---- task dispatch ---------------------------------------------
        # eligibility already cleared the structural gates (full RS /
        # tracker / rs_cap / in-order) and the speculative TLB/TM gate for
        # the granted stream — a granted TASK either dispatches or drains
        is_task = active & (op == isa.OP_TASK)
        in_s = jnp.where(ctl & isa.CTL_IN_INDIRECT, regs[a], a)
        out_s = jnp.where(ctl & isa.CTL_OUT_INDIRECT, regs[b], b)
        in_e, out_e = in_s + asz, out_s + bsz
        phys_in = remap(st, in_s)
        dep = tracker_lookup(st, phys_in, phys_in + (in_e - in_s))

        # drain path: TM full and a committed victim exists
        do_drain = is_task & spec & ~tm_avail
        vic_base = p.tm_base + st["tlb_slot"][victim] * p.tm_slot_words
        vic_s, vic_e = st["tlb_os"][victim], st["tlb_oe"][victim]
        st["mem_writes"] = st["mem_writes"] + do_drain.astype(I32)
        if base:
            st["mem"] = copy_window(st["mem"], st["mem"], vic_s, vic_base,
                                    vic_e - vic_s, do_drain)
        else:
            st["mem"] = copy_each(
                st["mem"], do_drain.astype(I32),
                lambda k, mem: copy_window(mem, mem, vic_s, vic_base,
                                           vic_e - vic_s, True))
        st["tlb_valid"] = st["tlb_valid"] & ~(do_drain
                                              & (l_iota == victim))
        st["fe_wait"] = jnp.where(gmask & do_drain, p.tlb_drain_cycles,
                                  st["fe_wait"])

        dispatch = is_task & ~do_drain
        phys_out = jnp.where(spec, p.tm_base + tm_slot * p.tm_slot_words, out_s)
        phys_oe = phys_out + (out_e - out_s)

        # TLB insert for speculative dispatch
        tlb_slot_new = jnp.argmin(st["tlb_valid"])
        ins_tlb = dispatch & spec
        tlb_sel = ins_tlb & (l_iota == tlb_slot_new)
        st["tlb_valid"] = st["tlb_valid"] | tlb_sel
        st["tlb_os"] = jnp.where(tlb_sel, out_s, st["tlb_os"])
        st["tlb_oe"] = jnp.where(tlb_sel, out_e, st["tlb_oe"])
        st["tlb_slot"] = jnp.where(tlb_sel, tm_slot, st["tlb_slot"])
        st["tlb_seq"] = jnp.where(tlb_sel, st["tlb_seq_ctr"], st["tlb_seq"])
        st["tlb_com"] = st["tlb_com"] & ~tlb_sel
        st["tlb_seq_ctr"] = st["tlb_seq_ctr"] + jnp.where(ins_tlb, 1, 0)

        # WAW replacement + tracker insert
        waw = dispatch & st["trk_valid"] & (st["trk_s"] < phys_oe) \
            & (phys_out < st["trk_e"])
        st["trk_valid"] = st["trk_valid"] & ~waw
        trk_new = jnp.argmin(st["trk_valid"])
        trk_sel = dispatch & (t_iota == trk_new)
        st["trk_valid"] = st["trk_valid"] | trk_sel
        st["trk_s"] = jnp.where(trk_sel, phys_out, st["trk_s"])
        st["trk_e"] = jnp.where(trk_sel, phys_oe, st["trk_e"])
        st["trk_uid"] = jnp.where(trk_sel, st["next_uid"], st["trk_uid"])
        st["trk_spec"] = jnp.where(trk_sel, spec, st["trk_spec"])

        # RS insert
        rs_new = jnp.argmin(st["rs_valid"])
        uid = st["next_uid"]
        st["overflow"] = st["overflow"] | (dispatch & (uid >= U)) \
            | (dispatch & (out_e - out_s > W))
        uidc = jnp.clip(uid, 0, U - 1)
        pidv = F["pid"][pcc]
        rs_sel = dispatch & (s_iota == rs_new)
        st["rs_valid"] = st["rs_valid"] | rs_sel
        for k, v in (("rs_uid", uid), ("rs_func", acc),
                     ("rs_dep", dep), ("rs_age", st["age"]),
                     ("rs_out_s", phys_out), ("rs_out_e", phys_oe),
                     ("rs_src", out_s), ("rs_exec", func_cycles[jnp.clip(acc, 0, NF - 1)]),
                     ("rs_pid", pidv)):
            st[k] = jnp.where(rs_sel, v, st[k])
        st["rs_spec"] = jnp.where(rs_sel, spec, st["rs_spec"])
        st["next_uid"] = st["next_uid"] + jnp.where(dispatch, 1, 0)
        st["age"] = st["age"] + jnp.where(dispatch, 1, 0)
        st["fe_wait"] = jnp.where(gmask & dispatch,
                                  c.dispatch_serial_cost - 1, st["fe_wait"])
        pc_next = jnp.where(dispatch, pc_g + 1, pc_next)
        progressed = progressed | dispatch

        # ---- if / branches ----------------------------------------------
        is_if = active & (op == isa.OP_IF) & ~st["br_active"]
        kind = ctl & 0x3
        cond = (ctl >> 2) & 0x3
        thr = regs[asz]
        # RR: resolve inline with a 1-cycle bubble
        rr = is_if & (kind == isa.BR_RR)
        rr_taken = eval_cond(cond, regs[a], thr)
        pc_next = jnp.where(rr, jnp.where(rr_taken, pc_g + b, pc_g + 1),
                            pc_next)
        st["fe_wait"] = jnp.where(gmask & rr, 1, st["fe_wait"])
        # MR/BR (eligibility already cleared the in-order empty-machine
        # requirement and the branch unit being free)
        mrbr = is_if & (kind != isa.BR_RR)
        phys_a = remap(st, a)
        wait_uid = tracker_lookup(st, phys_a, phys_a + 1)
        eff_kind = jnp.where((kind == isa.BR_BR) & (wait_uid == 0),
                             I32(isa.BR_MR), kind)
        speculate = jnp.bool_(c.speculation) & ~st["spec_active"]
        st["br_active"] = st["br_active"] | mrbr
        for k, v in (("br_kind", eff_kind), ("br_pc", pc_g), ("br_off", b),
                     ("br_cond", cond), ("br_thr", thr), ("br_addr", a),
                     ("br_wait", wait_uid), ("br_stream", gidx)):
            st[k] = jnp.where(mrbr, v, st[k])
        st["br_speculating"] = jnp.where(mrbr, speculate, st["br_speculating"])
        start_mr = mrbr & (eff_kind == isa.BR_MR)
        st["mr_active"] = st["mr_active"] | start_mr
        st["mr_rem"] = jnp.where(start_mr, p.mem_read_cycles, st["mr_rem"])
        enter_spec = mrbr & speculate
        st["spec_active"] = st["spec_active"] | enter_spec
        st["spec_ckpt"] = jnp.where(enter_spec, regs, st["spec_ckpt"])
        pc_next = jnp.where(enter_spec, pc_g + 1, pc_next)
        progressed = progressed | rr | mrbr

        st["pc"] = jnp.where(gmask, pc_next, pcs)
        st["stall_cycles"] = st["stall_cycles"] + jnp.where(
            progressed | ~alive, 0, 1)
        # the four dispatch trace writes share one (uid, enable) selector;
        # the caller applies them (select-form, scatter-form, or a fused
        # pallas kernel, per step_impl)
        fe = dict(uid=uidc, acc=acc, dep=dep, pid=pidv, dispatch=dispatch)
        return st, fe

    @_named("dispatch_traces")
    def dispatch_traces(st, fe, tw=None):
        if tw is not None or base:
            tw = tw or trace_write
            st["tr_func"] = tw(st["tr_func"], fe["uid"], fe["acc"],
                               fe["dispatch"])
            st["tr_dispatch"] = tw(st["tr_dispatch"], fe["uid"],
                                   st["cycle"], fe["dispatch"])
            st["tr_dep"] = tw(st["tr_dep"], fe["uid"], fe["dep"],
                              fe["dispatch"])
            st["tr_pid"] = tw(st["tr_pid"], fe["uid"], fe["pid"],
                              fe["dispatch"])
            return st
        sel = trace_sel(fe["uid"], fe["dispatch"])
        st["tr_func"] = trace_put(st["tr_func"], sel, fe["acc"])
        st["tr_dispatch"] = trace_put(st["tr_dispatch"], sel, st["cycle"])
        st["tr_dep"] = trace_put(st["tr_dep"], sel, fe["dep"])
        st["tr_pid"] = trace_put(st["tr_pid"], sel, fe["pid"])
        return st

    # ------------------------------------------------------------------
    # event-skip: time to the next scheduler event
    # ------------------------------------------------------------------
    @_named("next_dt")
    def next_dt(st, exists, F, p_len, rs_cap, streams):
        if not spec.event_skip:
            return I32(1)
        busy = st["fu_busy"] & exists
        cands = jnp.where(busy, st["fu_rem"], BIG)
        dt = jnp.min(cands)
        dt = jnp.minimum(dt, jnp.where(st["mr_active"], st["mr_rem"], BIG))
        cdb_dt = jnp.where(st["cdb_valid"],
                           jnp.maximum(st["cdb_ready"] - st["cycle"], 1), BIG)
        dt = jnp.minimum(dt, jnp.min(cdb_dt))
        dt = jnp.minimum(dt, jnp.min(jnp.where(st["fe_wait"] > 0,
                                               st["fe_wait"], BIG)))
        # any stream's frontend can act next cycle → no skipping
        NS = streams.shape[0]
        ns_iota = jnp.arange(NS, dtype=I32)
        s_end, s_arr = streams[:, 1], streams[:, 2]
        s_active = s_end > streams[:, 0]
        drained = st["pc"] >= s_end
        pccs = jnp.clip(st["pc"], 0, max(P - 1, 0))
        at_op = F["op"][pccs]
        in_order_block = (jnp.bool_(c.in_order) & ~machine_empty(st)
                          & ((at_op == isa.OP_TASK) | (at_op == isa.OP_IF)))
        # structural stall: a TASK blocked on a full RS / Memory Tracker /
        # its pid's RS admission cap can only unblock via an issue (covered
        # below) or a CDB grant (in the min) — skippable
        pid_here = F["pid"][pccs]
        pid_capped = ((st["rs_valid"][None, :]
                       & (st["rs_pid"][None, :] == pid_here[:, None]))
                      .sum(axis=1).astype(I32) >= rs_cap[pid_here])
        struct_block = ((at_op == isa.OP_TASK)
                        & (st["rs_valid"].all() | st["trk_valid"].all()
                           | pid_capped))
        br_mine = ns_iota == st["br_stream"]
        br_ok = jnp.where(st["br_active"],
                          jnp.where(st["br_speculating"], br_mine, ~br_mine),
                          True)
        fe_act = ((st["fe_wait"] == 0) & br_ok & s_active & ~drained
                  & (s_arr <= st["cycle"] + 1)
                  & ~in_order_block & ~struct_block)
        dt = jnp.where(fe_act.any(), 1, dt)
        # never skip across a stream arrival (frontend state changes there,
        # and the per-stream stall accounting relies on windows lying
        # entirely on one side of every arrival)
        arr_dt = jnp.where(s_active & ~drained & (s_arr > st["cycle"]),
                           s_arr - st["cycle"], BIG)
        dt = jnp.minimum(dt, jnp.min(arr_dt))
        # a ready RS entry with a free unit issues next cycle
        free = exists & ~st["fu_busy"]
        n_free = jnp.zeros((NF,), I32).at[fu_cls].add(free.astype(I32))
        ready = st["rs_valid"] & (st["rs_dep"] == 0)
        issue_now = (ready & (n_free[st["rs_func"]] > 0)).any()
        dt = jnp.where(issue_now, 1, dt)
        return jnp.clip(dt, 1, BIG)

    # ------------------------------------------------------------------
    # full step + driver
    # ------------------------------------------------------------------
    def alive_of(st):
        return (~st["halted"] & ~st["overflow"]
                & (st["cycle"] < spec.max_cycles))

    @_named("step_top")
    def step_top(st, streams, limit):
        # ``alive`` gates every phase: a halted/overflowed lane is a fixed
        # point of the step, so the batched population machine can run one
        # while-loop with a scalar any-lane-alive condition and NO
        # per-lane carry select (see ``run_population``).  In the single
        # machine the while condition implies alive == True, so the gates
        # are identities.  ``limit`` is the lane's *step-count* ceiling
        # for this entry (BIG = run to completion): a lane at its limit
        # freezes as a fixed point too, which is what lets ``run_slice``
        # pause and re-enter the loop with bit-exact composition.  The
        # ceiling counts steps (while-loop trips), not cycles, because
        # under event-skip a trip's cycle advance is arbitrary — steps
        # are the unit wall time is actually spent in, so a step ceiling
        # bounds a slice's cost where a cycle ceiling cannot (one
        # event-dense lane can burn hundreds of trips inside a modest
        # cycle window).
        alive = alive_of(st) & (st["steps"] < limit)
        st["steps"] = st["steps"] + jnp.where(alive, 1, 0)
        # Per-stream dispatch-stall accounting for the event-skipped window
        # behind this step (``dt - 1`` cycles with no events, hence no
        # grants).  It must read *pre-phase* state: the window's cycles lie
        # before this step's branch resolve, which may redirect a stream's
        # pc (squash) and flip its drained status.  next_dt clamps dt to
        # arrival boundaries, so a stream was either arrived for the whole
        # window or for none of it.
        w_stalled = ((streams[:, 1] > streams[:, 0])
                     & (st["pc"] < streams[:, 1])
                     & (streams[:, 2] <= st["cycle"] - st["dt"]))
        st["fe_stall"] = st["fe_stall"] + jnp.where(
            alive & w_stalled, st["dt"] - 1, 0)
        return st, alive

    @_named("step_bottom")
    def step_bottom(st, exists, F, p_len, rs_cap, streams, alive):
        done = ((st["pc"] >= streams[:, 1]).all() & ~st["rs_valid"].any()
                & ~st["fu_busy"].any()
                & ~st["cdb_valid"].any() & ~st["br_active"] & ~st["mr_active"]
                & (st["fe_wait"] == 0).all())
        dt = next_dt(st, exists, F, p_len, rs_cap, streams)
        st["cycle"] = st["cycle"] + jnp.where(alive,
                                              jnp.where(done, 1, dt), 0)
        st["dt"] = jnp.where(alive, dt, st["dt"])
        st["halted"] = st["halted"] | (alive & done)
        return st

    def step(st, exists, F, p_len, prio, quota, rs_cap, cost, eft, streams,
             effects, limit):
        st, alive = step_top(st, streams, limit)
        st, fu_done = fu_exec(st, exists, effects, alive)
        st = cdb_enqueue(st, fu_done, trace_write)
        st, br_ready = memread_tick(st, alive)
        st, br_ready = cdb_grant(st, br_ready, alive, trace_write)
        st, kills = branch_core(st, br_ready)
        st = abort_traces(st, kills, trace_write)
        st = rs_issue(st, exists, prio, quota, cost, eft, alive, trace_write)
        st, fe = frontend_core(st, F, p_len, rs_cap, streams, alive)
        st = dispatch_traces(st, fe)
        return step_bottom(st, exists, F, p_len, rs_cap, streams, alive)

    # ------------------------------------------------------------------
    # the pallas population step: the same phase functions, but the
    # scatter/select-heavy ones run as fused lane-per-program kernels
    # (pallas_step.py) over the whole population, and the rest are
    # vmapped.  ``pl.pallas_call`` cannot sit under ``jax.vmap``, which
    # is why this is a population-level step rather than a per-lane one.
    # Inside a kernel there is no batch axis, so the trace writes use the
    # single-machine scatter form (``tw_scatter``) — cheap per lane.
    # ------------------------------------------------------------------
    ENQ_KEYS = ("cdb_valid", "cdb_uid", "cdb_ticket", "cdb_ready",
                "cdb_spec", "ticket", "overflow", "tr_complete",
                "fu_busy", "fu_uid")
    GRANT_KEYS = ("cdb_valid", "rs_dep", "trk_valid", "tr_broadcast")
    ISSUE_KEYS = ("fu_busy", "fu_uid", "fu_rem", "fu_out_s", "fu_out_e",
                  "fu_src", "fu_spec", "fu_pid", "tr_issue", "rs_valid")
    TRACE_KEYS = ("tr_func", "tr_dispatch", "tr_dep", "tr_pid",
                  "tr_aborted")

    def make_pop_step():
        from . import pallas_step as ps

        def k_enqueue(v):
            return cdb_enqueue(v, v["done"], tw_scatter)

        def k_grant(v):
            st2, br = cdb_grant(v, v["br_ready"], v["alive"], tw_scatter,
                                unroll=True)
            st2["br_ready"] = br
            return st2

        def k_issue(v):
            return rs_issue(v, v["exists"], v["prio"], v["quota"],
                            v["cost"], v["eft"], v["alive"], tw_scatter)

        def k_traces(v):
            st2 = dict(v)
            st2 = abort_traces(
                st2, (v["rs_uid_k"], v["rs_kill"], v["fu_uid_k"],
                      v["fu_kill"]), tw_scatter)
            sel = jnp.where(v["dispatch"], v["uid"], U)
            for key, val in (("tr_func", v["acc"]),
                             ("tr_dispatch", v["cycle"]),
                             ("tr_dep", v["dep"]), ("tr_pid", v["pid"])):
                st2[key] = st2[key].at[sel].set(val, mode="drop")
            return st2

        def pop_step(st, exists, F, p_len, prio, quota, rs_cap, cost, eft,
                     streams, effects, limit):
            st, alive = jax.vmap(step_top)(st, streams, limit)
            st, fu_done = jax.vmap(fu_exec)(st, exists, effects, alive)

            ins = {k: st[k] for k in ENQ_KEYS}
            ins.update(done=fu_done, cycle=st["cycle"],
                       fu_spec=st["fu_spec"])
            with jax.named_scope("cdb_enqueue"):
                st.update(ps.lane_phase(k_enqueue, ins, ENQ_KEYS))

            st, fired = jax.vmap(memread_tick)(st, alive)

            ins = {k: st[k] for k in GRANT_KEYS}
            ins.update(cdb_ready=st["cdb_ready"], cdb_ticket=st["cdb_ticket"],
                       cdb_uid=st["cdb_uid"], cycle=st["cycle"],
                       trk_uid=st["trk_uid"], br_active=st["br_active"],
                       br_kind=st["br_kind"], br_wait=st["br_wait"],
                       br_ready=fired, alive=alive)
            with jax.named_scope("cdb_grant"):
                out = ps.lane_phase(k_grant, ins,
                                    GRANT_KEYS + ("br_ready",))
            br_ready = out.pop("br_ready")
            st.update(out)

            st, kills = jax.vmap(branch_core)(st, br_ready)
            rs_uid_k, rs_kill, fu_uid_k, fu_kill = kills

            ins = {k: st[k] for k in ISSUE_KEYS}
            ins.update(rs_dep=st["rs_dep"], rs_pid=st["rs_pid"],
                       rs_age=st["rs_age"], rs_func=st["rs_func"],
                       rs_uid=st["rs_uid"], rs_exec=st["rs_exec"],
                       rs_out_s=st["rs_out_s"], rs_out_e=st["rs_out_e"],
                       rs_src=st["rs_src"], rs_spec=st["rs_spec"],
                       cycle=st["cycle"], exists=exists, prio=prio,
                       quota=quota, cost=cost, eft=eft, alive=alive)
            with jax.named_scope("rs_issue"):
                st.update(ps.lane_phase(k_issue, ins, ISSUE_KEYS))

            st, fe = jax.vmap(frontend_core)(st, F, p_len, rs_cap, streams,
                                             alive)

            ins = {k: st[k] for k in TRACE_KEYS}
            ins.update(rs_uid_k=rs_uid_k, rs_kill=rs_kill,
                       fu_uid_k=fu_uid_k, fu_kill=fu_kill,
                       uid=fe["uid"], acc=fe["acc"], dep=fe["dep"],
                       pid=fe["pid"], dispatch=fe["dispatch"],
                       cycle=st["cycle"])
            # one kernel writes the abort and the dispatch traces
            with jax.named_scope("dispatch_traces"):
                st.update(ps.lane_phase(k_traces, ins, TRACE_KEYS))

            return jax.vmap(step_bottom)(st, exists, F, p_len, rs_cap,
                                         streams, alive)

        return pop_step

    # the population step: kernel-phased under pallas, plain vmap otherwise
    vstep = make_pop_step() if impl == "pallas" else jax.vmap(step)

    def norm_args(ftab, p_len, n_fu, prio, quota, rs_cap, fu_cost, eft,
                  streams):
        F = {name: ftab[..., i].astype(I32)
             for i, name in enumerate(isa.FIELDS)}
        p_len = jnp.asarray(p_len, I32)
        exists = fu_pos < n_fu[..., fu_cls]
        if prio is None:
            prio = jnp.zeros((NUM_PIDS,), I32)
        if quota is None:
            quota = jnp.full((NUM_PIDS,), BIG, I32)
        if rs_cap is None:
            rs_cap = jnp.full((NUM_PIDS,), BIG, I32)
        if fu_cost is None:
            # all-ones = every unit of a class identical (the paper's pool)
            cost = jnp.ones(p_len.shape + (NFU,), I32)
        else:
            fu_cost = jnp.asarray(fu_cost, I32)
            w = fu_cost.shape[-1]
            if w > spec.max_fu_per_class:
                # tables are packed at the canonical width (costs.
                # FU_COST_WIDTH); a narrower machine uses the prefix —
                # unit indices ≥ max_fu_per_class don't exist here
                fu_cost = fu_cost[..., :spec.max_fu_per_class]
            elif w < spec.max_fu_per_class:
                fu_cost = jnp.concatenate(
                    [fu_cost, jnp.ones(fu_cost.shape[:-1]
                                       + (spec.max_fu_per_class - w,), I32)],
                    axis=-1)
            # flatten (NF, max_fu) → (NFU,) row-major: matches fu_cls/fu_pos
            cost = fu_cost.reshape(fu_cost.shape[:-2] + (NFU,))
        eft = (jnp.zeros(p_len.shape, I32) if eft is None
               else jnp.asarray(eft, I32))
        if streams is None:
            # the historical single merged frontend: one stream covering
            # [0, p_len), arrival 0 (population form gets a leading axis)
            streams = (jnp.zeros(p_len.shape + (1, 4), I32)
                       .at[..., 0, 1].set(p_len))
        else:
            streams = jnp.asarray(streams, I32)
        return F, p_len, exists, prio, quota, rs_cap, cost, eft, streams

    def collect(st):
        return dict(
            cycles=st["cycle"], halted=st["halted"], overflow=st["overflow"],
            n_tasks=st["next_uid"] - 1, spec_aborted=st["spec_aborted"],
            stall_cycles=st["stall_cycles"], steps=st["steps"],
            mem_writes=st["mem_writes"],
            fe_stall=st["fe_stall"],
            fu_busy_cycles=st["fu_busy_cycles"],
            mem=st["mem"], regs=st["regs"],
            tr_func=st["tr_func"], tr_dispatch=st["tr_dispatch"],
            tr_issue=st["tr_issue"], tr_complete=st["tr_complete"],
            tr_broadcast=st["tr_broadcast"], tr_dep=st["tr_dep"],
            tr_aborted=st["tr_aborted"], tr_pid=st["tr_pid"],
        )

    def run(ftab, p_len, n_fu, mem_init, effects, prio=None, quota=None,
            rs_cap=None, fu_cost=None, eft=None, streams=None):
        F, p_len, exists, prio, quota, rs_cap, cost, eft, streams = norm_args(
            ftab, p_len, n_fu, prio, quota, rs_cap, fu_cost, eft, streams)
        effects = jnp.asarray(effects, I32)
        st = init_state(mem_init, streams)
        st = jax.lax.while_loop(
            lambda s: alive_of(s).any(),
            lambda s: step(s, exists, F, p_len, prio, quota, rs_cap,
                           cost, eft, streams, effects, BIG),
            st)
        return collect(st)

    def run_population(ftab, p_len, n_fu, mem_init, effects,
                       prio, quota, rs_cap, fu_cost=None, eft=None,
                       streams=None):
        """The scenario-batched machine: every argument carries a leading
        scenario axis, and the whole population runs in ONE while loop
        whose condition is scalar (any lane alive).  Because a dead lane
        is a fixed point of ``step``, no per-lane select over the carry is
        needed — which is what makes this markedly faster than
        ``vmap(run)`` (the generic batching of a while loop masks the
        whole ~25 KB/lane state every iteration)."""
        F, p_len, exists, prio, quota, rs_cap, cost, eft, streams = norm_args(
            ftab, p_len, n_fu, prio, quota, rs_cap, fu_cost, eft, streams)
        effects = jnp.asarray(effects, I32)
        st = jax.vmap(init_state)(jnp.asarray(mem_init, I32), streams)

        limit = jnp.full_like(p_len, BIG)
        st = jax.lax.while_loop(
            lambda s: alive_of(s).any(),
            lambda s: vstep(s, exists, F, p_len, prio, quota, rs_cap,
                            cost, eft, streams, effects, limit),
            st)
        return collect(st)

    # ------------------------------------------------------------------
    # resumable population machine: the same while loop, re-enterable
    # ------------------------------------------------------------------
    def init_population(ftab, p_len, n_fu, mem_init, effects,
                        prio, quota, rs_cap, fu_cost=None, eft=None,
                        streams=None):
        """The population while-loop carry, fresh: one state row per lane.

        Only ``pc`` (= each stream's start pc) and ``mem`` (= the memory
        image) depend on the arguments — every other field is a constant
        fill — which is the invariant lane refill relies on (a host can
        build a fresh row for a *different* program from any fresh row by
        overwriting just those two fields).  ``fu_cost``/``eft`` stay out
        of the carry for the same reason: like ``prio`` they are
        loop-invariant step inputs, re-supplied on every slice.
        """
        _, p_len, _, _, _, _, _, _, streams = norm_args(
            ftab, p_len, n_fu, prio, quota, rs_cap, fu_cost, eft, streams)
        return jax.vmap(init_state)(jnp.asarray(mem_init, I32), streams)

    def run_slice(carry, ftab, p_len, n_fu, mem_init, effects,
                  prio, quota, rs_cap, fu_cost, eft, streams, budget):
        """Advance every alive lane by at most ``budget`` machine steps.

        Per-lane limits are ``carry steps + budget`` at entry, so every
        lane pauses exactly at its ceiling and the returned carry feeds
        straight back in.  The budget counts *steps* (while-loop trips),
        not cycles: under event-skip a trip's cycle advance is data-
        dependent, so only a step ceiling bounds what a slice costs in
        wall time — which is the whole point of slicing.  ``budget`` is
        traced: sweeping it never recompiles.  ``mem_init`` is unused
        (the carry owns the memory image) but kept so the argument list
        stays exactly ``PackedPopulation.machine_args()``.
        """
        F, p_len, exists, prio, quota, rs_cap, cost, eft, streams = norm_args(
            ftab, p_len, n_fu, prio, quota, rs_cap, fu_cost, eft, streams)
        effects = jnp.asarray(effects, I32)
        limit = carry["steps"] + jnp.asarray(budget, I32)
        return jax.lax.while_loop(
            lambda s: (alive_of(s) & (s["steps"] < limit)).any(),
            lambda s: vstep(s, exists, F, p_len, prio, quota, rs_cap,
                            cost, eft, streams, effects, limit),
            carry)

    def run_one(ftab, p_len, n_fu, mem_init, effects, prio=None, quota=None,
                rs_cap=None, fu_cost=None, eft=None, streams=None):
        """Single-lane pallas machine: a population of one, squeezed.

        ``pl.pallas_call`` cannot sit under ``jax.vmap``, so the pallas
        step only exists in population form — the single machine lifts
        its arguments onto a width-1 scenario axis and drops it from the
        outputs.  Nones that ``norm_args`` would default *unbatched*
        (the pid tables) are defaulted here first."""
        if prio is None:
            prio = jnp.zeros((NUM_PIDS,), I32)
        if quota is None:
            quota = jnp.full((NUM_PIDS,), BIG, I32)
        if rs_cap is None:
            rs_cap = jnp.full((NUM_PIDS,), BIG, I32)

        def lift(x):
            return None if x is None else jnp.asarray(x)[None]
        out = run_population(lift(ftab), lift(p_len), lift(n_fu),
                             lift(mem_init), lift(effects), lift(prio),
                             lift(quota), lift(rs_cap), lift(fu_cost),
                             lift(eft), lift(streams))
        return jax.tree.map(lambda x: x[0], out)

    if resumable:
        if not population:
            raise ValueError("resumable=True requires population=True")
        return ResumableMachine(init=init_population, run_slice=run_slice,
                                collect=collect)
    if population:
        return run_population
    return run_one if impl == "pallas" else run


@functools.lru_cache(maxsize=32)
def _compiled(spec: MachineSpec, max_prog: int):
    return jax.jit(make_machine(spec, max_prog))


def pack_program(code: np.ndarray, max_prog: int) -> tuple[np.ndarray, int]:
    """Decode + zero-pad a program to the machine's static table shape."""
    tbl = isa.decode_table(code)
    p_len = len(tbl)
    if p_len > max_prog:
        raise ValueError(f"program length {p_len} > max_prog {max_prog}")
    pad = np.zeros((max_prog, tbl.shape[1]), np.int32)
    pad[:p_len] = tbl
    # padding rows decode as acc-id 0 tasks but are never fetched (pc >= p_len)
    return pad, p_len


def images(params: HtsParams, mem_init=None, effects=None):
    mem = np.zeros((params.total_mem,), np.int32)
    eff = np.zeros((params.total_mem,), np.int32)
    for k, v in (mem_init or {}).items():
        mem[k] = v
    for k, v in (effects or {}).items():
        eff[k] = v
    return mem, eff


def simulate(code: np.ndarray, costs: SchedulerCosts,
             params: HtsParams = HtsParams(),
             n_fu=None, mem_init=None, effects=None,
             event_skip: bool = True, max_cycles: int = 5_000_000,
             max_fu_per_class: int = 16, max_prog: int = 256,
             policy: SchedPolicy | None = None,
             fu_cost=None,
             streams=None, step_impl: str = "xla") -> dict[str, Any]:
    """One-shot convenience wrapper around the cached compiled machine.

    ``policy`` (defaulting to ``params.policy``) is lowered to the traced
    ``prio``/``quota`` runtime arrays — the compiled machine is shared
    across policies, so sweeping weights never recompiles.  ``fu_cost``
    (defaulting to ``params.fu_cost``) is the per-(class, unit) latency
    table, and the policy's ``issue_mode`` lowers to the traced ``eft``
    flag — both runtime data too, so heterogeneous cost sweeps and
    greedy/EFT flips share the one compilation.  ``streams`` is the
    optional (n_streams, 4) per-tenant frontend table
    (``frontend.STREAM_FIELDS``); ``None`` = one merged frontend.
    """
    pol = policy if policy is not None else params.policy
    cost = fu_cost if fu_cost is not None else params.fu_cost
    # the policy and cost table reach the machine as runtime data, never as
    # part of the compilation key — canonicalise them out of the cached
    # MachineSpec
    ms = MachineSpec(params=dataclasses.replace(params, policy=SchedPolicy(),
                                                fu_cost=None),
                     costs=costs, event_skip=event_skip,
                     max_cycles=max_cycles, max_fu_per_class=max_fu_per_class,
                     step_impl=step_impl)
    run = _compiled(ms, max_prog)
    ftab, p_len = pack_program(code, max_prog)
    n_fu = jnp.asarray(n_fu if n_fu is not None else params.n_fu, I32)
    mem, eff = images(params, mem_init, effects)
    out = run(jnp.asarray(ftab), p_len, n_fu, jnp.asarray(mem),
              jnp.asarray(eff), jnp.asarray(pol.weight_array(), I32),
              jnp.asarray(pol.quota_array(), I32),
              jnp.asarray(pol.rs_cap_array(), I32),
              jnp.asarray(norm_fu_cost(cost), I32),
              jnp.asarray(1 if pol.issue_mode == "eft" else 0, I32),
              None if streams is None else jnp.asarray(streams, I32))
    return jax.tree.map(np.asarray, out)


def schedule_tuple(out: dict[str, Any]) -> list[tuple]:
    """Match golden.Result.schedule_tuple() for equivalence tests."""
    n = int(out["n_tasks"])
    rows = []
    for uid in range(1, n + 1):
        rows.append((uid, int(out["tr_func"][uid]), int(out["tr_dispatch"][uid]),
                     int(out["tr_issue"][uid]), int(out["tr_complete"][uid]),
                     int(out["tr_broadcast"][uid]), bool(out["tr_aborted"][uid]),
                     int(out["tr_pid"][uid])))
    return rows
