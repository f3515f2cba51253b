"""The runtime's own spans and copy counters, read back from a profiler
trace on the CPU.

A small 'pallas-jit' deployment with shared halos, a cache it overflows
and a lock pass runs a few iterations under ``jax.profiler``; the kernels
the runtime does not reach at this size are called directly in the same
window.  The trace is reduced with the benchmark's own reader
(``chipbench.trace.load``).
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from chipbench import trace  # noqa: E402
from repro.core import make_runtime  # noqa: E402
from repro.kernels import protocol_sweep as ps  # noqa: E402
from repro.utils.trace import KERNELS, SPAN_NAMES  # noqa: E402

W, PW = 16, 64
BLK = 8 * PW
N = W * BLK
JITTED = {"phase_step": "_phase_step_jit", "take_and_cut": "_take_and_cut_jit",
          "popcount": "_popcount_rows_jit",
          "take_first_k": "_take_first_k_jit",
          "kth_set_index": "_kth_set_index_jit",
          "coverage": "_coverage_multi_jit"}


def _nbytes(arrays):
    return sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
               for a in arrays)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Run the deployment under the profiler with every jitted kernel
    wrapped to record the shapes it is given and returns."""
    sent, got, shapes = [], [], []
    real = {k: getattr(ps, v) for k, v in JITTED.items()}

    def recording(kernel):
        def call(*args):
            out = real[kernel](*args)
            outs = out if isinstance(out, tuple) else (out,)
            sent.append(_nbytes(args))
            got.append(_nbytes(outs))
            shapes.append((kernel, [a.shape for a in args]))
            return out
        return call

    mp = pytest.MonkeyPatch()
    for k, v in JITTED.items():
        mp.setattr(ps, v, recording(k))
    log_dir = tmp_path_factory.mktemp("trace")
    rt = make_runtime(W, backend="pallas-jit", page_words=PW, cache_pages=12,
                      fetch_batch=4)
    A, B, C = rt.alloc(N), rt.alloc(N), rt.alloc(4 * N)
    lo = np.arange(W) * BLK
    hi = lo + BLK
    direct: dict = {}
    rng = np.random.default_rng(7)
    bits = rng.integers(0, 2 ** 32, (8, 4), dtype=np.uint32)
    k = rng.integers(0, 64, 8)
    try:
        jax.profiler.start_trace(str(log_dir))
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            for _ in range(3):
                # halo reads of A shared with both neighbours, B written
                rt.phase_all(reads=[(A, np.maximum(lo - PW, 0),
                                     np.minimum(hi + PW, N))],
                             writes=[(B, lo, hi)])
                rt.barrier()
                # a stream through C four times the cache: eviction
                rt.phase_all(reads=[(C, 4 * lo, 4 * hi)],
                             writes=[(A, lo, hi)])
                rt.barrier()
                rt.span_all(lock_ids=0, reads=[(B, lo, lo + 4)],
                            writes=[(B, lo, lo + 4)])
                rt.barrier()
            ps.take_and_cut(bits, k, backend="pallas-jit", stats=direct)
            ps.take_first_k(bits, k, backend="pallas-jit", stats=direct)
            ps.kth_set_index(bits, k, backend="pallas-jit", stats=direct)
            ps.popcount_rows(bits, backend="pallas-jit", stats=direct)
            ps.coverage_multi(np.array([1, 1, -1, 1, -1, -1]),
                              backend="pallas-jit", stats=direct)
        jax.profiler.stop_trace()
    finally:
        mp.undo()
    tr = trace.load(trace.find_xplane(str(log_dir)), SPAN_NAMES)
    return tr, rt.stats, direct, sent, got, shapes


def _inside(inner, outers):
    return any(o.start <= inner.start and inner.end <= o.end for o in outers)


def test_every_program_span_appears(traced):
    tr = traced[0]
    assert set(SPAN_NAMES) == {s.name for s in tr.spans} - {
        trace.WINDOW_SPAN}
    assert {f"kernel.{k}" for k in KERNELS} <= set(SPAN_NAMES)
    assert set(JITTED) == set(KERNELS)


def test_flush_spans_nest(traced):
    tr = traced[0]
    by = {}
    for s in tr.spans:
        by.setdefault(s.name, []).append(s)
    for name in ("regc.flush.pack", "regc.flush.apply", "kernel.phase_step"):
        assert all(_inside(s, by["regc.flush"]) for s in by[name]), name
    # a barrier flushes once; a lock pass may hoist its own flush
    assert all(_inside(s, by["regc.barrier"] + by["regc.span"])
               for s in by["regc.flush"])
    assert all(sum(_inside(f, [b]) for f in by["regc.flush"]) == 1
               for b in by["regc.barrier"])
    assert len(by["regc.barrier"]) == 9 and len(by["regc.phase"]) == 6
    assert all(_inside(s, by["regc.phase"]) for s in by["regc.evict"])
    # every kernel dispatch of the runtime runs inside one of its calls
    api = by["regc.phase"] + by["regc.span"] + by["regc.barrier"]
    assert sum(_inside(s, api) for s in tr.spans
               if s.name.startswith("kernel.")) == traced[1][
                   "jit_dispatches"]


def test_copy_counters_equal_the_dispatched_bytes(traced):
    _, stats, direct, sent, got, shapes = traced
    n = stats["jit_dispatches"] + direct["jit_dispatches"]
    assert len(sent) == n
    assert stats["jit_h2d_bytes"] + direct["jit_h2d_bytes"] == sum(sent)
    assert stats["jit_d2h_bytes"] + direct["jit_d2h_bytes"] == sum(got)
    # the fused flush sends its packed (R, W, nw) planes, the int32 window
    # geometry and the bool row mask, and returns int32 counts and the
    # packed candidate planes
    flush = [(s, g, sh) for s, g, (kn, sh) in zip(sent, got, shapes)
             if kn == "phase_step"]
    assert len(flush) == stats["jit_phase_step"] > 0
    for s, g, sh in flush:
        R, Wr, nw = sh[0]
        assert Wr == W
        assert s == 4 * R * W * nw + 3 * 4 * R * W + R * W
        assert g == 4 * R * W + 4 * R * W * nw
