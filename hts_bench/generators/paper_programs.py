"""The paper's evaluation programs, in the order the traffic file lists
them, as one call; ``random_dependency`` draws its DAG from the run's
seed."""
from hts_bench import gen


def generate(seed: int, t: dict, seconds: float) -> gen.Traffic:
    progs = [(gen.random_dependency(seed=[seed])
              if n == "random_dependency" else gen.PAPER_PROGRAMS[n]()
              ).build() for n in t["programs"]]
    return gen.Traffic(progs, [list(range(len(progs)))])
