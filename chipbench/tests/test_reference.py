"""The plain reference agrees with the program where it should.

    python -m pytest -q chipbench/tests

Small deployments drawn from fixed seeds, with pages shared by two
workers' blocks, caches from a few pages to more than the working set,
and dirty evictions: the plain reference (``chipbench/reference/dsm.py``)
and the runtime under test, on its numpy tier, must give the same traffic
field for field and clocks within the configurations' limit.  With
``fetch_batch`` 16 the comparison keeps to page-aligned blocks: where an
op in the runtime's mid-op refetch path has partial edge pages it charges
them as one request, where its other path charges one per edge page (the
reference follows the latter).
"""
import numpy as np
import pytest

from chipbench import harness
from chipbench.kinds import dsm


def program_run(cell, seed: int, iters: int):
    from repro.core import make_runtime
    from repro.dsm import costmodel
    from repro.dsm.session import session
    cfg = cell.config
    rt = make_runtime(int(cfg["workers"]), backend="numpy",
                      cost=getattr(costmodel, cfg["cost_model"]),
                      **dsm.runtime_kwargs(cfg))
    drv = dsm.SpanDriver(session(rt, cfg["driver"]), rt.alloc)
    prog = dsm.build_program(cell, drv, seed)
    for _ in range(iters):
        prog.iteration()
    return dsm.traffic_of(rt), np.array(rt.clock, np.float64)


def deployment(case: int):
    rng = np.random.default_rng(case)
    W = int(rng.integers(2, 9))
    pw = int(rng.choice([16, 64]))
    fb = 1 if case % 3 else 16
    if case % 2:
        chunk = int(rng.integers(3, 400))
        if fb > 1:
            chunk = pw * int(rng.integers(1, 12))
        n = W * chunk + (0 if fb > 1 else int(rng.integers(0, W)))
        cache = int(rng.integers(1, 3 * (-(-chunk // pw)) + 20))
        return "stream.spill", {"workers": W, "array_words": n,
                                "cache_pages": cache, "page_words": pw,
                                "fetch_batch": fb}
    n = int(rng.integers(W, 200))
    if fb > 1:
        n = W * int(rng.integers(1, 6))
        pw = n
    return "jacobi.weak", {"workers": W, "grid_n": n, "page_words": pw,
                           "fetch_batch": fb}


@pytest.mark.parametrize("case", range(24))
def test_reference_agrees_with_the_program(case):
    name, over = deployment(case)
    cell = harness.load_cell(name)
    cell.config.update(over)
    seed = 2 ** 31 + 1000 * case + 7
    got_t, got_c = program_run(cell, seed, 4)
    ref_t, ref_c = dsm.replay(cell, seed, 4)
    checks = dsm.compare(got_t, got_c, ref_t, ref_c, cell.config["limits"])
    assert got_t == ref_t, over
    assert checks["clock_gap"]["value"] <= checks["clock_gap"]["limit"], over
