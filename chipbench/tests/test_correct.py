"""The check that decides ``correct`` fails where it must.

    python -m pytest -q chipbench/tests

The control (the reference with its clocks in float32, the precision
below the configuration's float64) must read as not correct, and so must
a run of the harness whose timed path is broken underneath, once for
each fault a DSM cell can have.  The chip's look is skipped; sizes are
the rehearsal's.
"""
import numpy as np
import pytest

from chipbench import harness
from chipbench.kinds import dsm
from chipbench.tests.test_rehearsal import TINY, run_tiny


@pytest.mark.parametrize("name", sorted(TINY))
@pytest.mark.parametrize("seed", [1, 2 ** 31 + 11, 987654321])
def test_control_is_not_correct(name, seed):
    cell = harness.load_cell(name)
    cell.config.update(TINY[name])
    ctl = dsm.control_readings(cell, seed, {"iterations": 3})["control"]
    assert ctl["traffic_gap"]["value"] == 0
    assert ctl["clock_gap"]["value"] > ctl["clock_gap"]["limit"]


def _flush_fault(monkeypatch, alter):
    import repro.kernels.protocol_sweep as ps
    inner = ps.phase_step

    def phase_step(*a, **kw):
        counts, shared = inner(*a, **kw)
        return alter(np.array(counts), np.array(shared))
    monkeypatch.setattr(ps, "phase_step", phase_step)


def _not_correct(name):
    res = run_tiny(name, False)
    assert not res["correct"], res["checks"]
    assert res["failed"] == res["attempted"]
    return res["checks"]


@pytest.mark.parametrize("name", sorted(TINY))
def test_flush_that_returns_its_state_unchanged(name, monkeypatch):
    """The barrier flush kernel hands back no counts and no candidates:
    nothing is written back or invalidated."""
    _flush_fault(monkeypatch, lambda c, s: (np.zeros_like(c),
                                            np.zeros_like(s)))
    _not_correct(name)


@pytest.mark.parametrize("name", sorted(TINY))
def test_answer_altered_where_produced(name, monkeypatch):
    """One worker's dirty count is off by one as the kernel returns it."""
    def alter(c, s):
        c.reshape(-1)[0] += 1
        return c, s
    _flush_fault(monkeypatch, alter)
    _not_correct(name)


@pytest.mark.parametrize("name", sorted(TINY))
def test_half_the_workers_left_out(name, monkeypatch):
    """Each phase carries the writes of only the first half of the
    workers; the rest run with empty write sets."""
    from repro.dsm import session as sess_mod
    inner = sess_mod._phase_callable

    def phase_callable(rt, driver):
        phase = inner(rt, driver)
        half = rt.W // 2

        def cut(reads=(), writes=(), **kw):
            writes = [(ga, lo, np.where(np.arange(rt.W) < half, hi, lo))
                      for ga, lo, hi in writes]
            return phase(reads=reads, writes=writes, **kw)
        return cut
    monkeypatch.setattr(sess_mod, "_phase_callable", phase_callable)
    _not_correct(name)
