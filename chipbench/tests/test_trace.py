"""The trace reduction, the byte function and the peaks table.

    python -m pytest -q chipbench/tests

The recorded trace under ``chipbench/testdata`` is one traced window of
``jacobi.weak`` on one TPU v5e; the numbers it must give were read from
it by hand.
"""
from pathlib import Path

import json
import pytest

from chipbench import flops, trace
from chipbench.harness import Refused, peaks_for
from chipbench.kinds import dsm

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"


def test_union_clip_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (4.0, 4.5), (6.0, 6.0)]
    assert trace.union(iv) == [(0.0, 2.0), (3.0, 4.5)]
    assert trace.clip(trace.union(iv), 1.0, 3.5) == [(1.0, 2.0), (3.0, 3.5)]
    assert trace.gaps(trace.union(iv), -1.0, 5.0) == [
        (-1.0, 0.0), (2.0, 3.0), (4.5, 5.0)]
    assert trace.length(trace.union(iv)) == pytest.approx(3.5)


def _synthetic():
    E = trace.Event
    dev = "/device:TPU:0"
    ops = {dev: [E("fusion.1", 1.0, 2.0), E("fusion.2", 1.5, 2.5),
                 E("copy", 4.0, 4.5), E("fusion.1", 9.5, 11.0)]}
    modules = {dev: [E("jit__phase_step_jit(1)", 1.0, 2.5),
                     E("jit__popcount_rows_jit(2)", 4.0, 4.5),
                     E("jit_other(3)", 9.5, 11.0)]}
    spans = [E(trace.WINDOW_SPAN, 0.0, 10.0),
             E("session.phase", 0.0, 3.0),
             E("session.barrier", 3.0, 10.0)]
    return trace.Trace((0.0, 10.0), spans, ops, modules)


def test_busy_idle_and_kernels_synthetic():
    tr = _synthetic()
    assert tr.window_s == 10.0
    # busy: [1, 2.5) + [4, 4.5) + [9.5, 10) clipped at the window's end
    assert tr.busy_s() == pytest.approx(2.5)
    assert tr.module_s([r"_phase_step_jit"]) == pytest.approx(1.5)
    assert tr.module_s(list(dsm.KERNEL_MODULES.values())) == pytest.approx(
        2.0)
    assert tr.op_s([r"^copy$"]) == pytest.approx(0.5)
    # idle gaps, each put down to the span open at its middle: [0, 1)
    # under phase; [2.5, 4) and [4.5, 9.5) under barrier
    assert dict(tr.idle_gaps()) == pytest.approx(
        {"session.phase": 1.0, "session.barrier": 6.5})
    assert tr.top_ops(3) == [["jit__phase_step_jit/fusion.1", 1.0],
                             ["jit__phase_step_jit/fusion.2", 1.0],
                             ["jit__popcount_rows_jit/copy", 0.5]]
    # an op nested in another counts once: the outer keeps its self time
    E = trace.Event
    nested = trace.self_times(
        [E("%while.3 = (s32[]) while(...)", 0.0, 1.0),
         E("%fusion.4 = s32[8] fusion(...)", 0.2, 0.9)],
        [E("jit_k(7)", 0.0, 1.0)], 0.0, 1.0)
    assert nested == [("jit_k/%while.3", pytest.approx(0.3)),
                      ("jit_k/%fusion.4", pytest.approx(0.7))]
    assert tr.span_s(["session.barrier"]) == pytest.approx(7.0)


def test_flush_min_bytes():
    # jacobi.weak: 256 workers x 16384 pages x 2 written arrays
    assert flops.flush_min_bytes(2 * 256 * 16384) == 2 * 1048576
    assert flops.flush_min_bytes(1) == 2
    assert flops.flush_min_bytes(9) == 4


def test_peaks_table():
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(Refused):
        peaks_for("cpu")


def _recorded():
    path = TESTDATA / "jacobi.weak.xplane.pb"
    if not path.exists():
        pytest.fail(f"the recorded trace {path} is missing")
    return trace.load(str(path), dsm.SPAN_NAMES), json.loads(
        (TESTDATA / "jacobi.weak.expected.json").read_text())


def test_recorded_trace():
    tr, want = _recorded()
    assert tr.devices() == want["devices"]
    assert tr.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert tr.busy_s() == pytest.approx(want["busy_s"], rel=1e-9)
    assert tr.module_s(list(dsm.KERNEL_MODULES.values())) == pytest.approx(
        want["kernel_s"], rel=1e-9)
    assert tr.module_s([dsm.KERNEL_MODULES["phase_step"]]) == pytest.approx(
        want["phase_step_s"], rel=1e-9)
    assert dict(tr.idle_gaps()) == pytest.approx(want["idle_gaps"],
                                                 rel=1e-9)
    assert 0 < tr.busy_s() <= tr.window_s
    assert sum(dict(tr.idle_gaps()).values()) == pytest.approx(
        tr.window_s - tr.busy_s(), rel=1e-9)
