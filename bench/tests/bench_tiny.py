"""Cells of BENCHMARK.json cut to a size the CPU test run can hold: the
cell's own traffic, limits and accuracy parameters, with fewer particles,
smaller leaves and the Pallas kernels in interpret mode."""
import copy
import time

from bench import harness

TINY_N, TINY_LEAF = 3000, 64


def tiny_cell(name: str, backend: str = "pallas_interpret"):
    cell = harness.load_cell(name)
    conf = copy.deepcopy(cell.config)
    conf["system"]["n"] = TINY_N
    conf["treecode"].update(leaf_size=TINY_LEAF, batch_size=TINY_LEAF,
                            backend=backend)
    cell.config = conf
    return cell


def run_tiny(name: str, seed: int = 2**33 + 7, trace: bool = False,
             seconds: float = 0.2, **kw) -> dict:
    """One run of the harness on the CPU, past its look for a chip."""
    return harness.run_cell(tiny_cell(name, kw.pop("backend",
                                                   "pallas_interpret")),
                            seed, seconds, trace,
                            t_start=time.perf_counter(),
                            require_chip=False, **kw)
