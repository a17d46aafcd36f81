"""Plain cycle-by-cycle reference of the HTS machine, for the benchmark's check.

Written from the paper (arXiv:1907.00271, §IV-C and §V) and from the
numbers the configuration files state (Table-II latencies, scheduler cost
models, structure sizes).  It imports nothing of the system under test:
it decodes the 128-bit Table-I instruction words itself and steps every
cycle, with no event skipping, no batching and no compiled code.

Scope: the deployments the benchmark runs.  One merged dispatch stream,
the RS arbiter with per-pid priority weights, FU quotas and RS admission
caps (``policy``, below; with none it is the paper's age order),
identical units within a class.  A scenario or policy outside that scope
is refused, not approximated.

A policy is plain data, ``{"weights": {pid: w}, "quotas": {pid: q},
"rs_caps": {pid: c}}``, each key optional (pids may be strings, as JSON
writes them).  Its rules, as the program's ``SchedPolicy`` states them:

* RS issue takes ready entries in order of the key
  ``(4096 - clip(weight, 0, 4096)) * 2**17 + age``: the higher weight
  first, age order within a weight.  A pid that is not listed weighs 0.
* A pid that already holds ``quota`` busy units of an entry's class (units
  issued earlier in the same cycle count) has that entry skipped without
  using up the unit, so the arbiter stays work-conserving.  The issue
  width and the lowest-free-unit rule are unchanged.
* At dispatch, a TASK whose pid holds ``rs_cap`` RS entries stalls the
  frontend exactly as a full RS does.

``"fe_mode"`` may be given only as ``"rr"`` and ``"issue_mode"`` only as
``"greedy"``: per-tenant frontends and per-unit costs are outside the
scope.  No policy, or one that lists no quota, no cap and no weight other
than 0, gives the age-order answers.

Within one cycle the phases run in this order: FU tick, memory-read tick,
CDB grant (ticket order), branch resolve (commit or squash), RS issue,
frontend dispatch, halt check.
"""
from __future__ import annotations

import collections
import dataclasses

# Table I opcodes: accelerator ids at or above 0xF0 are control instructions
OP_TASK, OP_ADD, OP_MUL, OP_MOV, OP_JUMP, OP_IF, OP_LBEG, OP_LEND, OP_NOP = \
    range(9)
_CTRL = {0xF0: OP_NOP, 0xF1: OP_ADD, 0xF2: OP_MUL, 0xF3: OP_MOV,
         0xF4: OP_JUMP, 0xF5: OP_IF, 0xF6: OP_LBEG, 0xF7: OP_LEND}
BR_RR, BR_MR, BR_BR = 0, 1, 2                   # register, memory, bus read
CND_EQ, CND_NEQ, CND_GE = 0, 1, 2               # 3 = LE
CTL_IN_INDIRECT, CTL_OUT_INDIRECT, CTL_IMM = 1, 2, 1
PRIO_CAP, AGE_SPAN = 4096, 1 << 17             # the RS issue key's terms
POLICY_KEYS = {"weights", "quotas", "rs_caps", "fe_mode", "issue_mode"}


@dataclasses.dataclass(frozen=True)
class Instr:
    op: int
    acc: int
    a: int
    asz: int
    b: int
    bsz: int
    pid: int
    ctl: int


def decode(code) -> list[Instr]:
    """(P, 4) little-endian uint32 words -> instructions (Table I layout)."""
    out = []
    for w0, w1, w2, _ in ((int(x) for x in row) for row in code):
        acc = w0 & 0xFF
        op = _CTRL.get(acc, OP_TASK)
        out.append(Instr(op=op, acc=acc if op == OP_TASK else 0,
                         a=(w0 >> 8) & 0xFFFF, asz=(w0 >> 24) & 0xFF,
                         b=w1 & 0xFFFF, bsz=(w1 >> 16) & 0xFF,
                         pid=(w1 >> 28) & 0xF, ctl=w2 & 0xF))
    return out


@dataclasses.dataclass
class _Task:
    uid: int
    func: int
    dispatch: int
    pid: int
    issue: int = -1
    complete: int = -1
    broadcast: int = -1
    aborted: bool = False

    def row(self) -> tuple:
        return (self.uid, self.func, self.dispatch, self.issue,
                self.complete, self.broadcast, self.aborted, self.pid)


@dataclasses.dataclass
class _Entry:                   # one reservation-station entry
    uid: int
    func: int
    dep: int
    key: int                    # issue order: weight, then age
    pid: int
    out_s: int
    out_e: int
    src_s: int
    spec: bool


@dataclasses.dataclass(frozen=True)
class Outcome:
    cycles: int
    halted: bool
    rows: tuple                 # (uid, func, dispatch, issue, complete,
    #                             broadcast, aborted, pid) per task


def _policy(policy) -> tuple:
    """(weights, quotas, rs_caps) of a plain-data policy, each a dict of
    int pid to int value; refuses what the reference does not model."""
    policy = policy or {}
    unknown = set(policy) - POLICY_KEYS
    if unknown:
        raise ValueError(f"reference: policy keys {sorted(unknown)} are not "
                         f"modelled (known: {sorted(POLICY_KEYS)})")
    if policy.get("fe_mode", "rr") != "rr":
        raise ValueError(f"reference: fe_mode {policy['fe_mode']!r}: only "
                         "'rr' is modelled, since the reference runs one "
                         "merged dispatch stream")
    if policy.get("issue_mode", "greedy") != "greedy":
        raise ValueError(f"reference: issue_mode {policy['issue_mode']!r}: "
                         "only 'greedy' is modelled, since the reference's "
                         "units within a class are identical")

    weights, quotas, rs_caps = (
        {int(p): int(v) for p, v in (policy.get(key) or {}).items()}
        for key in ("weights", "quotas", "rs_caps"))
    for key, caps in (("quotas", quotas), ("rs_caps", rs_caps)):
        if any(v < 1 for v in caps.values()):
            raise ValueError(f"reference: {key} {caps}: a cap under 1 lets "
                             "the pid run nothing")
    return ({p: min(max(w, 0), PRIO_CAP) for p, w in weights.items()},
            quotas, rs_caps)


def simulate(code, mem_init: dict, effects: dict, *, hw: dict, sched: dict,
             func_cycles: list, n_fu, policy=None,
             max_cycles: int = 5_000_000) -> Outcome:
    """Run one scenario; return its cycle count and per-task schedule rows.

    ``hw`` holds the HTS structure sizes, ``sched`` one scheduler cost
    model and ``func_cycles`` the Table-II latency of each function class,
    all as a configuration file states them; ``policy`` is the scheduling
    policy as plain data (module docstring), ``None`` for age order.
    """
    weights, quotas, rs_caps = _policy(policy)
    prog = decode(code)
    end = len(prog)
    n_fu = [int(k) for k in n_fu]
    num_regs, mem_words = hw["num_regs"], hw["mem_words"]
    tm_slots, tm_words = hw["tm_slots"], hw["tm_slot_words"]
    in_order = sched["in_order"]
    serial = sched["dispatch_serial_cost"]
    extra = sched["completion_extra"]
    speculation = sched["speculation"]
    issue_width, cdb_width = sched["issue_width"], sched["cdb_width"]

    regs = [0] * num_regs
    mem = [0] * (mem_words + tm_slots * tm_words)
    eff = [0] * len(mem)
    for k, v in mem_init.items():
        mem[int(k)] = int(v)
    for k, v in effects.items():
        eff[int(k)] = int(v)

    fu_cls = [c for c, k in enumerate(n_fu) for _ in range(k)]
    n_units = len(fu_cls)
    fu_task = [None] * n_units          # (uid, out_s, out_e, src_s, spec)
    fu_rem = [0] * n_units

    pc = 0
    fe_wait = 0
    cycle = 0
    next_uid = 1
    age = 0
    ticket = 0
    rs: list[_Entry] = []
    tracker: list[list] = []            # [start, end, uid, spec]
    tlb: list[dict] = []                # os, oe, slot, committed, seq
    tlb_seq = 0
    tm_free = list(range(tm_slots))
    cdb: list[list] = []                # [uid, ticket, ready_cycle, spec]
    memread = 0                         # remaining cycles, 0 = idle
    br = None                           # the one branch unit
    spec_on = False
    regs_ckpt = None
    tasks: list[_Task] = []
    by_uid: dict[int, _Task] = {}

    def remap(addr):
        hit = [e for e in tlb if e["os"] <= addr < e["oe"]]
        if not hit:
            return addr
        e = max(hit, key=lambda x: x["seq"])
        return mem_words + e["slot"] * tm_words + addr - e["os"]

    def producer(s, e):
        return max((t[2] for t in tracker if t[0] < e and s < t[1]),
                   default=0)

    def holds(cond, v, thr):
        return (v == thr if cond == CND_EQ else v != thr if cond == CND_NEQ
                else v >= thr if cond == CND_GE else v <= thr)

    def empty():
        return (not rs and all(t is None for t in fu_task) and not cdb
                and not memread and br is None)

    def dispatch(ins, dep, out_s, out_e, src_s, spec):
        nonlocal next_uid, age
        tracker[:] = [t for t in tracker
                      if not (t[0] < out_e and out_s < t[1])]
        tracker.append([out_s, out_e, next_uid, spec])
        key = (PRIO_CAP - weights.get(ins.pid, 0)) * AGE_SPAN + age
        rs.append(_Entry(next_uid, ins.acc, dep, key, ins.pid, out_s, out_e,
                         src_s, spec))
        t = _Task(next_uid, ins.acc, cycle, ins.pid)
        tasks.append(t)
        by_uid[next_uid] = t
        next_uid += 1
        age += 1

    while cycle < max_cycles:
        # 1. FU tick
        for u in range(n_units):
            if fu_task[u] is not None:
                fu_rem[u] -= 1
                if fu_rem[u] == 0:
                    uid, out_s, out_e, src_s, spec = fu_task[u]
                    for j in range(out_e - out_s):
                        mem[out_s + j] = eff[src_s + j]
                    cdb.append([uid, ticket, cycle + extra, spec])
                    ticket += 1
                    by_uid[uid].complete = cycle
                    fu_task[u] = None
        # 2. memory-read tick
        value_ready = False
        if memread:
            memread -= 1
            value_ready = memread == 0
        # 3. CDB grant, in completion-ticket order
        for _ in range(cdb_width):
            ready = [e for e in cdb if e[2] <= cycle]
            if not ready:
                break
            e = min(ready, key=lambda x: x[1])
            cdb.remove(e)
            uid = e[0]
            by_uid[uid].broadcast = cycle
            for r in rs:
                if r.dep == uid:
                    r.dep = 0
            tracker[:] = [t for t in tracker if t[2] != uid]
            if br is not None and br["kind"] == BR_BR and br["wait"] == uid:
                value_ready = True
        # 4. branch resolve
        if br is not None and value_ready:
            taken = holds(br["cond"], mem[remap(br["addr"])], br["thr"])
            target = br["pc"] + (br["off"] if taken else 1)
            if br["spec"] and not taken:        # predicted right: commit
                for t in tlb:
                    t["committed"] = True
                for t in tracker:
                    t[3] = False
                for r in rs:
                    r.spec = False
                for u in range(n_units):
                    if fu_task[u] is not None:
                        fu_task[u] = fu_task[u][:4] + (False,)
                for e in cdb:
                    e[3] = False
            elif br["spec"]:                    # mispredicted: squash
                tm_free += [t["slot"] for t in tlb if not t["committed"]]
                tlb[:] = [t for t in tlb if t["committed"]]
                tracker[:] = [t for t in tracker if not t[3]]
                for r in rs:
                    if r.spec:
                        by_uid[r.uid].aborted = True
                rs[:] = [r for r in rs if not r.spec]
                for u in range(n_units):
                    if fu_task[u] is not None and fu_task[u][4]:
                        by_uid[fu_task[u][0]].aborted = True
                        fu_task[u] = None
                cdb[:] = [e for e in cdb if not e[3]]
                regs = list(regs_ckpt)
                pc = target
                fe_wait = 0
            else:
                pc = target
            if br["spec"]:
                spec_on = False
                regs_ckpt = None
            br = None
        # 5. RS issue: ready entries by weight, then oldest first, lowest
        # free unit; an entry whose pid holds its quota of busy units of
        # the class is skipped
        issued = 0
        if quotas:                      # (pid, class) -> busy units
            busy = collections.Counter(
                (by_uid[t[0]].pid, fu_cls[u])
                for u, t in enumerate(fu_task) if t is not None)
        for r in sorted(rs, key=lambda r: r.key):
            if issued >= issue_width:
                break
            if r.dep:
                continue
            free = [u for u in range(n_units)
                    if fu_cls[u] == r.func and fu_task[u] is None]
            if not free:
                continue
            if quotas:
                if busy[r.pid, r.func] >= quotas.get(r.pid, n_units):
                    continue
                busy[r.pid, r.func] += 1
            fu_task[free[0]] = (r.uid, r.out_s, r.out_e, r.src_s, r.spec)
            fu_rem[free[0]] = func_cycles[r.func]
            by_uid[r.uid].issue = cycle
            rs.remove(r)
            issued += 1
        # 6. frontend: fetch, decode and dispatch one instruction
        ok = pc < end and fe_wait == 0 and (br is None or br["spec"])
        ins = prog[pc] if ok else None
        if ok and ins.op == OP_TASK:
            if in_order and not empty():
                ok = False
            elif len(rs) >= hw["rs_entries"] or \
                    len(tracker) >= hw["tracker_entries"] or \
                    ins.pid in rs_caps and \
                    sum(r.pid == ins.pid for r in rs) >= rs_caps[ins.pid]:
                ok = False
            elif spec_on:
                if not tm_free:
                    ok = any(t["committed"] for t in tlb)
                elif len(tlb) >= hw["tlb_entries"]:
                    ok = False
        elif ok and ins.op == OP_IF:
            if br is not None:
                ok = False
            elif (ins.ctl & 3) != BR_RR and in_order and not empty():
                ok = False
        if fe_wait > 0:
            fe_wait -= 1
        if ok:
            op = ins.op
            if op == OP_TASK:
                in_s = regs[ins.a] if ins.ctl & CTL_IN_INDIRECT else ins.a
                out_s = regs[ins.b] if ins.ctl & CTL_OUT_INDIRECT else ins.b
                out_e = out_s + ins.bsz
                phys = remap(in_s)
                dep = producer(phys, phys + ins.asz)
                if not spec_on:
                    dispatch(ins, dep, out_s, out_e, out_s, False)
                    fe_wait = serial - 1
                    pc += 1
                elif not tm_free:
                    # TM full: write back the oldest committed entry; the
                    # frontend spends the drain time and dispatches nothing
                    v = min((t for t in tlb if t["committed"]),
                            key=lambda t: t["seq"])
                    base = mem_words + v["slot"] * tm_words
                    for j in range(v["oe"] - v["os"]):
                        mem[v["os"] + j] = mem[base + j]
                    tm_free.append(v["slot"])
                    tlb.remove(v)
                    fe_wait = hw["tlb_drain_cycles"]
                else:
                    slot = min(tm_free)
                    tm_free.remove(slot)
                    tlb.append({"os": out_s, "oe": out_e, "slot": slot,
                                "committed": False, "seq": tlb_seq})
                    tlb_seq += 1
                    phys_out = mem_words + slot * tm_words
                    dispatch(ins, dep, phys_out, phys_out + out_e - out_s,
                             out_s, True)
                    fe_wait = serial - 1
                    pc += 1
            elif op == OP_ADD:
                regs[ins.b] = regs[ins.a] + regs[ins.asz]
                pc += 1
            elif op == OP_MUL:
                regs[ins.b] = regs[ins.a] * regs[ins.asz]
                pc += 1
            elif op == OP_MOV:
                regs[ins.b] = ins.a if ins.ctl & CTL_IMM else regs[ins.a]
                pc += 1
            elif op == OP_JUMP:
                pc = ins.a
            elif op == OP_LBEG:
                regs[ins.asz] = regs[ins.a] if ins.ctl & 1 else ins.a
                pc += 1
            elif op == OP_LEND:
                regs[ins.asz] -= 1
                pc = pc - ins.b if regs[ins.asz] > 0 else pc + 1
            elif op == OP_IF:
                kind, cond = ins.ctl & 3, (ins.ctl >> 2) & 3
                thr = regs[ins.asz]
                if kind == BR_RR:
                    pc = pc + ins.b if holds(cond, regs[ins.a], thr) \
                        else pc + 1
                    fe_wait = 1                 # one-cycle bubble
                else:
                    phys = remap(ins.a)
                    wait = producer(phys, phys + 1)
                    if kind == BR_BR and wait == 0:
                        kind = BR_MR            # producer already retired
                    spec = speculation and not spec_on
                    br = {"kind": kind, "pc": pc, "off": ins.b, "cond": cond,
                          "thr": thr, "addr": ins.a, "wait": wait,
                          "spec": spec}
                    if kind == BR_MR:
                        memread = hw["mem_read_cycles"]
                    if spec:
                        spec_on = True
                        regs_ckpt = list(regs)
                        pc += 1                 # predict not taken
            else:
                pc += 1
        cycle += 1
        # 7. halt check
        if pc >= end and empty() and fe_wait == 0:
            return Outcome(cycle, True, tuple(t.row() for t in tasks))
    return Outcome(cycle, False, tuple(t.row() for t in tasks))
