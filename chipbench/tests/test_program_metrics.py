"""The readers of the program's own spans and copy counters.

    python -m pytest -q chipbench/tests

Hand-built traces check the self-time arithmetic and the window clip; a
trace written by the profiler on the CPU checks that the program's spans
are read back and name the idle gaps; the CPU rehearsal checks that a
traced run reports every new metric in the cells that list it.
"""
import sys
import time

import pytest

from chipbench import harness, program_spans, trace
from chipbench.kinds import dsm

E = trace.Event
PROGRAM_METRICS = ("flush_host_ms", "evict_host_ms", "kernel_call_ms")


def _read(name, ctx):
    return harness.layer_reader(name)(ctx)


def _ctx(spans, window=(0.0, 10.0), iters=2):
    return {"iters": iters,
            "program_trace": trace.Trace(window, spans, {}, {})}


def test_self_time_less_nested_kernels():
    ctx = _ctx([E("regc.barrier", 0.5, 4.5),
                E("regc.flush", 1.0, 4.0),
                E("regc.flush.pack", 1.0, 2.0),
                E("kernel.phase_step", 2.0, 3.0),
                E("regc.flush.apply", 3.0, 4.0),
                E("regc.phase", 5.0, 7.5),
                E("regc.evict", 5.0, 7.0),
                E("kernel.popcount", 5.5, 6.0),
                E("kernel.take_first_k", 6.5, 6.75)])
    # flush 3 s less its 1-s kernel, over 2 iterations
    assert _read("flush_host_ms", ctx) == pytest.approx(1000.0)
    # eviction 2 s less 0.75 s of kernels
    assert _read("evict_host_ms", ctx) == pytest.approx(625.0)
    # the kernels' union, wherever they ran
    assert _read("kernel_call_ms", ctx) == pytest.approx(875.0)


def test_overlapping_spans_count_once():
    tr = trace.Trace((0.0, 10.0), [E("regc.flush", 1.0, 4.0),
                                   E("regc.flush", 3.0, 5.0),
                                   E("kernel.phase_step", 2.0, 3.5),
                                   E("kernel.popcount", 3.0, 4.5)], {}, {})
    assert program_spans.self_s(tr, ["regc.flush"],
                                program_spans.kernel_names()) == \
        pytest.approx(4.0 - 2.5)
    assert program_spans.subtract([(0.0, 2.0), (3.0, 6.0)],
                                  [(1.0, 3.5), (4.0, 5.0)]) == [
        (0.0, 1.0), (3.5, 4.0), (5.0, 6.0)]


def test_clipped_to_the_window():
    ctx = _ctx([E("regc.flush", -2.0, 1.0),
                E("kernel.phase_step", -1.0, 0.5),
                E("regc.flush", 9.0, 12.0),
                E("kernel.phase_step", 9.5, 11.0)], iters=1)
    # [0, 1) less [0, 0.5), and [9, 10) less [9.5, 10)
    assert _read("flush_host_ms", ctx) == pytest.approx(1000.0)
    assert _read("kernel_call_ms", ctx) == pytest.approx(1000.0)
    # a span wholly outside the window reads as absent
    assert _read("evict_host_ms",
                 _ctx([E("regc.evict", 11.0, 12.0)])) is None


def test_none_where_the_program_emits_no_spans(monkeypatch):
    # spans the program did not emit in this window
    ctx = _ctx([E("regc.phase", 1.0, 2.0)])
    for name in PROGRAM_METRICS:
        assert _read(name, ctx) is None
    # a program without span names (the parent commit): nothing to load
    monkeypatch.setitem(sys.modules, "repro.utils.trace", None)
    assert program_spans.names() is None
    tr = trace.Trace((0.0, 1.0), [], {}, {})
    for name in PROGRAM_METRICS:
        assert _read(name, {"iters": 1, "trace": tr}) is None
    assert tr.spans == []
    # and no copy counters
    assert _read("xfer_bytes", {"iters": 2,
                                "counters": {"jit_dispatches": 4}}) is None


def test_xfer_bytes_per_iteration():
    assert _read("xfer_bytes", {"iters": 4, "counters": {
        "jit_h2d_bytes": 3000, "jit_d2h_bytes": 1000}}) == 1000.0


def test_program_spans_name_the_idle_gaps(tmp_path, monkeypatch):
    """A profiler trace with harness and program spans, its device plane
    built by hand on the trace's clock: once a reader has loaded the
    program's spans, each idle gap goes to the innermost one."""
    jax = pytest.importorskip("jax")
    from jax.profiler import TraceAnnotation as ann
    jax.profiler.start_trace(str(tmp_path / "run"))
    with ann(trace.WINDOW_SPAN):
        with ann("session.barrier"):
            with ann("regc.barrier", at=1):
                with ann("regc.flush"):
                    with ann("regc.flush.pack", regions=1, words=4):
                        time.sleep(0.02)
                    with ann("kernel.phase_step", shape=(1, 16, 4)):
                        time.sleep(0.02)
                    with ann("regc.flush.apply"):
                        time.sleep(0.03)
    jax.profiler.stop_trace()
    tr = trace.load(trace.find_xplane(str(tmp_path / "run")),
                    dsm.SPAN_NAMES)
    assert {s.name for s in tr.spans} == {trace.WINDOW_SPAN,
                                          "session.barrier"}
    prog = trace.load(trace.find_xplane(str(tmp_path / "run")),
                      program_spans.names())
    k = next(s for s in prog.spans if s.name == "kernel.phase_step")
    dev = "/device:TPU:0"
    tr.ops[dev] = [E("fusion", k.start, k.end)]
    tr.modules[dev] = [E("jit__phase_step_jit(1)", k.start, k.end)]
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    ctx = {"trace": tr, "iters": 1}
    assert _read("flush_host_ms", ctx) == pytest.approx(
        program_spans.self_s(prog, ["regc.flush"], [k.name]) * 1e3)
    assert _read("kernel_call_ms", ctx) == pytest.approx(
        (k.end - k.start) * 1e3)
    gaps = dict(tr.idle_gaps())
    assert {"regc.flush.pack", "regc.flush.apply"} <= set(gaps)
    assert gaps["regc.flush.apply"] > gaps["regc.flush.pack"]
    assert "kernel.phase_step" not in gaps


@pytest.mark.parametrize("name", ["jacobi.weak", "stream.spill"])
def test_traced_cell_reports_program_metrics(name, tmp_path, monkeypatch):
    from test_rehearsal import run_tiny
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    res = run_tiny(name, True)
    assert res["correct"]
    want = {"flush_host_ms", "kernel_call_ms", "xfer_bytes"}
    if name == "stream.spill":
        want.add("evict_host_ms")
    assert want <= set(res["metrics"])
    assert "evict_host_ms" not in res["metrics"] or name == "stream.spill"
    assert all(res["metrics"][m]["value"] > 0 for m in want)
    assert res["metrics"]["xfer_bytes"]["unit"] == "bytes/iter"
