"""The benchmark's traffic generator and the programs it builds.

Every traffic mix is a JSON file of parameters under ``hts_bench/traffic/``.
Its ``generator`` key names a kind, ``hts_bench/generators/<kind>.py``, whose
``generate(seed, traffic, seconds)`` turns the rest of the file into a
:class:`Traffic`; a mix that offers requests over time names its arrival
process the same way (``hts_bench/arrivals/<kind>.py``).  A new mix of an
existing kind is a new JSON file, and a new kind a new module beside the
others: nothing here changes.  Programs are built only through the public
``hts.Program`` builder, so edits to the repository's own generators
(``core/hts/workloads.py``, ``core/hts/programs.py``, ``benchmarks/``) do
not move the yardstick.

The scenario and paper-program builders below are copies of
``workloads.generate_scenario`` (with its default options: no policy, one
merged frontend, uniform units), of the paper programs of
``programs.all_benches()`` and of the QoS policy grid of
``benchmarks/population.build_grid``; ``hts_bench/tests/test_gen.py``
pins the copies to the originals.  A scheduling policy travels as plain
data (``reference.py``'s form), one per program, in ``Traffic.policies``.
"""
from __future__ import annotations

import dataclasses
import importlib

import numpy as np

INPUT = 0x10                    # shared input frame address
INPUT_WORDS = 4
TENANT_BASE = 0x40              # first tenant region base
MEM_WORDS = 1024                # generator address space (the memory image)
ALIGN = 0x8                     # region allocator alignment
RSTRIDE = 0x8                   # paper programs' region stride
BAND_WORDS = 0x20               # audio pipeline per-band footprint
SHAPES = ("chain", "fanout", "mixed", "loop", "branch")
#: the paper's task mix (Table II keynames), used round-robin (§V-B)
MIX = ("real_fir", "complex_fir", "adaptive_fir", "vector_dot", "iir",
       "vector_add", "vector_max", "fft_256", "dct", "correlation")


def _program(name, **kw):
    from repro.core import hts
    return hts.Program(name, **kw)


# ---------------------------------------------------------------------------
# generated multi-tenant scenarios
# ---------------------------------------------------------------------------
class _Tenant:
    def __init__(self, pid: int, base: int, span: int, reg_budget: int):
        self.prog = _program(f"tenant{pid}", region_base=base)
        self.pid = pid
        self.words_left = span
        self.regs_left = reg_budget
        self.frame = self.prog.input(INPUT, INPUT_WORDS, "frame")

    def take(self, regs: int, words: int) -> bool:
        words = -(-words // ALIGN) * ALIGN
        if regs > self.regs_left or words > self.words_left:
            return False
        self.regs_left -= regs
        self.words_left -= words
        return True


def _straight(rng, t: _Tenant, kernels, n: int, chain: bool) -> None:
    prev = t.frame
    for i in range(n):
        if not t.take(0, 4):
            return
        h = t.prog.task(str(rng.choice(kernels)), in_=prev, out=4,
                        in_size=4, tid=i & 0xF)
        if chain or rng.random() < 0.2:
            prev = h


def _loop(rng, t: _Tenant, kernels) -> bool:
    iters = int(rng.integers(2, 5))
    if not t.take(3, iters * ALIGN):
        return False
    w = t.prog.walker(stride=ALIGN, count=iters, name=f"w{t.pid}")
    with t.prog.loop(iters):
        t.prog.task(str(rng.choice(kernels)), in_=t.frame, out=w,
                    out_size=4, tid=1)
        w.advance()
    return True


def _branch(rng, t: _Tenant, kernels) -> bool:
    n_each = int(rng.integers(1, 3))
    if not t.take(1, ALIGN + n_each * 2 * ALIGN):
        return False
    kind = str(rng.choice(("mem", "bus")))
    taken = bool(rng.random() < 0.5)
    cond = t.prog.region(1, name=f"cond{t.pid}")
    if kind == "bus":
        t.prog.task("correlation", in_=t.frame, out=cond, tid=0)
        cond.effect(9 if taken else 1)
    else:
        cond.init(9 if taken else 1)
    br = t.prog.branch(on=cond, cond=">=", thr=5, kind=kind)
    with br.not_taken():
        for i in range(n_each):
            t.prog.task(str(rng.choice(kernels)), in_=t.frame, out=4,
                        tid=i & 0xF)
    with br.taken():
        for i in range(n_each):
            t.prog.task(str(rng.choice(kernels)), in_=t.frame, out=4,
                        tid=(i + 4) & 0xF)
    return True


def _tenant(rng, pid, base, span, reg_budget, kernels, max_tasks):
    t = _Tenant(pid, base, span, reg_budget)
    shape = str(rng.choice(SHAPES))
    with t.prog.process(pid):
        if shape == "loop" and not _loop(rng, t, kernels):
            shape = "chain"
        elif shape == "branch" and not _branch(rng, t, kernels):
            shape = "fanout"
        if shape in ("chain", "fanout"):
            _straight(rng, t, kernels, int(rng.integers(2, max_tasks + 1)),
                      chain=shape == "chain")
        elif shape == "mixed":
            _straight(rng, t, kernels, int(rng.integers(1, 3)), chain=True)
            if rng.random() < 0.5:
                _loop(rng, t, kernels)
            else:
                _straight(rng, t, kernels, int(rng.integers(1, 3)),
                          chain=False)
        else:
            _straight(rng, t, kernels, int(rng.integers(0, 2)), chain=False)
    return t.prog


def scenario(seed, *, kernels, max_tasks: int, n_tenants=None, name=None):
    """One seeded multi-tenant scenario, merged N-way; returns the
    ``hts.Program``.  ``seed`` is anything ``numpy.random.default_rng``
    takes (an int, or a list of ints)."""
    from repro.core import hts
    rng = np.random.default_rng(seed)
    if n_tenants is None:
        n_tenants = int(rng.integers(2, 9))
    span = ((MEM_WORDS - TENANT_BASE) // n_tenants) // ALIGN * ALIGN
    reg_budget = 31 // n_tenants
    tenants = [_tenant(rng, pid, TENANT_BASE + i * span, span, reg_budget,
                       kernels, max_tasks)
               for i, pid in enumerate(range(1, n_tenants + 1))]
    return hts.Program.merge(tenants, name or f"scenario_{seed}",
                             require_distinct_pids=True)


# ---------------------------------------------------------------------------
# the paper's evaluation programs (§VI: nine synthetic + Algorithm 1)
# ---------------------------------------------------------------------------
def _mix_program(name):
    p = _program(name)
    return p, p.input(INPUT, INPUT_WORDS, "frame")


def no_dependency(n: int = 20):
    p, frame = _mix_program("no_dependency")
    for i in range(n):
        p.task(MIX[i % len(MIX)], in_=frame, out=4, tid=i)
    return p


def same_dependency(chains: int = 4, depth: int = 5):
    p, frame = _mix_program("same_dependency")
    for c in range(chains):
        prev = frame
        for d in range(depth):
            prev = p.task(MIX[c % len(MIX)], in_=prev, out=4, in_size=4,
                          tid=d)
    return p


def diff_dependency(chains: int = 4, depth: int = 5):
    p, frame = _mix_program("diff_dependency")
    k = 0
    for _ in range(chains):
        prev = frame
        for d in range(depth):
            prev = p.task(MIX[k % len(MIX)], in_=prev, out=4, in_size=4,
                          tid=d)
            k += 1
    return p


def random_dependency(n: int = 24, seed=0, p_dep: float = 0.5):
    rng = np.random.default_rng(seed)
    p, frame = _mix_program("random_dependency")
    handles = []
    for i in range(n):
        src = (handles[int(rng.integers(0, i))]
               if i > 0 and rng.random() < p_dep else frame)
        func = MIX[int(rng.integers(0, len(MIX)))]
        handles.append(p.task(func, in_=src, out=4, in_size=4, tid=i))
    return p


def loop_no_dependency(iters: int = 8, body: int = 3):
    p, frame = _mix_program("loop_no_dependency")
    w = p.walker(stride=body * RSTRIDE, count=iters, name="out")
    with p.loop(iters):
        for j in range(body):
            p.task(MIX[j % len(MIX)], in_=frame,
                   out=w if j == 0 else w.offset(j * RSTRIDE),
                   out_size=4, tid=j)
        w.advance()
    return p


def loop_dependency(iters: int = 8):
    p, frame = _mix_program("loop_dependency")
    pre = p.task("fft_256", in_=frame, out=4, tid=0)
    w = p.walker(stride=RSTRIDE, count=iters, name="out")
    with p.loop(iters):
        p.task("iir", in_=pre, out=w, out_size=4, tid=1)
        w.advance()
    return p


def _branch_bench(name: str, taken: bool, kind: str, n_each: int = 6):
    p, frame = _mix_program(name)
    thr = p.let(5, "thr")
    cond = p.region(1, name="cond")
    if kind == "bus":
        p.task("correlation", in_=frame, out=cond, tid=0)
        cond.effect(9 if taken else 1)
    else:
        cond.init(9 if taken else 1)
    br = p.branch(on=cond, cond=">=", thr=thr, kind=kind)
    with br.not_taken():
        for i in range(n_each):
            p.task(MIX[i % len(MIX)], in_=frame, out=4, tid=i)
    with br.taken():
        for i in range(n_each):
            p.task(MIX[(i + 3) % len(MIX)], in_=frame, out=4, tid=i)
    p.task("vector_max", in_=frame, out=1, tid=0xF)
    return p


def branch_taken_no_dep():
    return _branch_bench("branch_taken_no_dep", True, "mem")


def branch_not_taken_no_dep():
    return _branch_bench("branch_not_taken_no_dep", False, "mem")


def branch_taken_dependency():
    return _branch_bench("branch_taken_dependency", True, "bus")


def audio_compression(bands: int = 8, time_domain: bool = False):
    """Algorithm 1; ``time_domain=True`` is the mis-speculated arm."""
    p = _program(f"audio_compression_{'bt' if time_domain else 'bnt'}")
    frame = p.input(INPUT, INPUT_WORDS, "audio")
    corr = p.task("correlation", in_=frame, out=1, tid=0)
    corr.out.effect(9 if time_domain else 1)
    thr = p.let(5, "thr")
    bandspace = p.region(bands * BAND_WORDS, name="bands")
    br = p.branch(on=corr.out, cond=">=", thr=thr, kind="bus")
    with br.not_taken():
        w = p.walker(start=bandspace.addr, stride=BAND_WORDS, name="band")
        with p.loop(bands):
            fft_o, dot_o, ifft_o = (w.offset(k) for k in (0x8, 0x10, 0x18))
            p.task("fft_256", in_=w, out=fft_o, in_size=4, out_size=4, tid=1)
            for j in range(3):
                p.task("vector_dot", in_=fft_o, out=dot_o, in_size=4,
                       out_size=1, tid=2 + j)
            p.task("fft_256", in_=dot_o, out=ifft_o, in_size=4, out_size=4,
                   tid=5)
            w.advance()
    with br.taken():
        w = p.walker(start=bandspace.addr, stride=BAND_WORDS, name="band")
        with p.loop(bands):
            outs = [w.offset(k) for k in (0x8, 0x10, 0x18)]
            for j, o in enumerate(outs):
                p.task("real_fir", in_=w, out=o, in_size=4, out_size=4,
                       tid=1 + j)
            w.advance()
    return p


PAPER_PROGRAMS = {
    "no_dependency": no_dependency,
    "same_dependency": same_dependency,
    "diff_dependency": diff_dependency,
    "random_dependency": random_dependency,
    "loop_no_dependency": loop_no_dependency,
    "loop_dependency": loop_dependency,
    "branch_taken_no_dep": branch_taken_no_dep,
    "branch_not_taken_no_dep": branch_not_taken_no_dep,
    "branch_taken_dependency": branch_taken_dependency,
    "audio_compression_bnt": lambda: audio_compression(8, False),
    "audio_compression_bt": lambda: audio_compression(8, True),
}


# ---------------------------------------------------------------------------
# the QoS policy grid: a latency-sensitive chain behind greedy floods, under
# a grid of scheduling policies (the paper's N-CPU sharing, Sec. III)
# ---------------------------------------------------------------------------
HI_PID = 1                      # the latency-sensitive tenant


def _hi_chain(chain: int = 8, delay: int = 10):
    """Latency-sensitive tenant: a RAW chain arriving after ``delay``
    nops."""
    p = _program("hi", region_base=0x100)
    frame = p.input(INPUT, INPUT_WORDS, "frame")
    for _ in range(delay):
        p.nop()
    with p.process(HI_PID):
        prev = frame
        for i in range(chain):
            prev = p.task("dct", in_=prev, out=4, in_size=4, tid=i)
    return p


def _greedy(pid: int, tasks: int = 10):
    """Best-effort flood: independent same-class tasks."""
    p = _program(f"greedy{pid}", region_base=0x180 + 0x80 * (pid - 2))
    frame = p.input(INPUT, INPUT_WORDS, "frame")
    with p.process(pid):
        for i in range(tasks):
            p.task("dct", in_=frame, out=4, tid=i & 0xF)
    return p


def _contended(n_greedy: int):
    from repro.core import hts
    return hts.Program.merge(
        [_hi_chain()] + [_greedy(2 + k) for k in range(n_greedy)],
        f"contended_{n_greedy}g", require_distinct_pids=True)


def qos_grid(mixes=(2, 3, 4, 5), weights=(0, 1, 2, 8), quotas=(None, 1),
             rs_caps=(None, 4)) -> list:
    """(built program, policy) of every instance of the tenant-mix x policy
    grid, in ``build_grid``'s order: per mix of greedy floods, pid 1's
    priority weight, the floods' FU quota and their RS cap (``None`` or 0:
    not set).  A policy is plain data and ``{}`` where nothing is set."""
    out = []
    for g in mixes:
        built = _contended(g).build()
        floods = range(2, 2 + g)
        for w in weights:
            for q in quotas:
                for rc in rs_caps:
                    pol = {}
                    if w:
                        pol["weights"] = {HI_PID: w}
                    if q:
                        pol["quotas"] = {p: q for p in floods}
                    if rc:
                        pol["rs_caps"] = {p: rc for p in floods}
                    out.append((built, pol))
    return out


# ---------------------------------------------------------------------------
# what a generator kind returns, and the lookup by name
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Traffic:
    """What one run offers the system: built programs and their order."""
    programs: list                   # hts BuiltProgram, one per scenario
    calls: list = dataclasses.field(default_factory=list)
    #: per call: indices into ``programs`` (batch drivers)
    arrivals: np.ndarray = None      # due times in seconds (serve driver)
    requests: np.ndarray = None      # per arrival: index into ``programs``
    #: per program: its scheduling policy as plain data, or ``None`` for
    #: age order (batch drivers); ``None`` here: no program has one
    policies: list = None


def kind(package: str, name: str):
    """The module ``hts_bench/<package>/<name>.py``."""
    return importlib.import_module(f"hts_bench.{package}.{name}")


def generate(seed: int, traffic: dict, seconds: float) -> Traffic:
    """The traffic a run of ``seconds`` offers, from its seed."""
    return kind("generators", traffic["generator"]).generate(
        seed, traffic, seconds)


def due_times(rng: np.random.Generator, arrivals: dict,
              seconds: float) -> np.ndarray:
    """Due times in ``[0, seconds)`` of the arrival process the traffic
    file's ``arrivals`` entry names by its ``kind``."""
    due = kind("arrivals", arrivals["kind"]).due_times(rng, arrivals,
                                                       seconds)
    return due[due < seconds]
