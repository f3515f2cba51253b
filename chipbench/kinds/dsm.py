"""DSM cells: a user program over ``make_runtime`` + ``Session``.

The configuration gives the deployment (workers, sizes, protocol, cache,
cost model, tier, driver); the traffic file names the user program
(``chipbench/programs/<program>.py``) and its mix.  The window drives the
program's ``Session`` on the tier the configuration states.  The check
replays the same iterations on the plain reference
(``chipbench/reference/dsm.py``) and compares every traffic field and
every worker's modeled clock.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from chipbench import flops
from chipbench.reference.dsm import PlainDSM

SPAN_NAMES = ("session.phase", "session.span", "session.reduce",
              "session.barrier")
# the protocol kernels as the trace names their jitted programs
KERNEL_MODULES = {
    "phase_step": r"_phase_step_jit",
    "take_and_cut": r"_take_and_cut_jit",
    "popcount": r"_popcount_rows_jit",
    "take_first_k": r"_take_first_k_jit",
    "kth_set_index": r"_kth_set_index_jit",
    "coverage": r"_coverage_multi_jit",
}
FLUSH_KERNEL = "phase_step"


class SpanDriver:
    """The ``Session`` surface.  In the window it times each call into
    ``calls``; in a traced run it also opens a harness span around it."""

    def __init__(self, sess, alloc):
        self.sess, self.alloc = sess, alloc
        self.calls: Optional[list] = None
        self.traced = False

    def _call(self, name, fn, *a, **kw):
        if self.calls is None:
            return fn(*a, **kw)
        t0 = time.perf_counter()
        if self.traced:
            import jax
            with jax.profiler.TraceAnnotation(name):
                fn(*a, **kw)
        else:
            fn(*a, **kw)
        self.calls.append((name, time.perf_counter() - t0))

    def phase(self, **kw):
        self._call("session.phase", self.sess.phase, **kw)

    def span(self, lock_ids, reads=(), writes=(), w_mask=None):
        self._call("session.span", self.sess.span, lock_ids, reads=reads,
                   writes=writes, w_mask=w_mask)

    def reduce(self, name, value=1.0):
        self._call("session.reduce", self.sess.reduce, name, value)

    def barrier(self):
        self._call("session.barrier", self.sess.barrier)


def runtime_kwargs(cfg: dict) -> dict:
    return dict(page_words=int(cfg["page_words"]),
                protocol=cfg["protocol"],
                cache_pages=cfg["cache_pages"],
                prefetch=int(cfg["prefetch"]),
                fetch_batch=int(cfg["fetch_batch"]),
                detect_races=False)


def build_program(cell, drv, seed: int):
    """The user program that the traffic file names,
    ``chipbench/programs/<program>.py``, at the configuration's sizes, with
    the placement (which worker owns which block) drawn from ``seed``."""
    from chipbench.harness import load_file
    W = int(cell.config["workers"])
    rng = np.random.default_rng(seed % (1 << 64))
    placement = (rng.permutation(W) if cell.traffic["placement"] == "seeded"
                 else np.arange(W))
    mod = load_file("programs", cell.traffic["program"], cell.root)
    return mod.Program(drv, cell.config, cell.traffic, placement)


def replay(cell, seed: int, iters: int, clock_dtype=np.float64):
    """The plain reference's traffic fields and clocks after ``iters``
    iterations of the cell's program.  A ``clock_dtype`` below float64
    gives the control."""
    ref = PlainDSM(cell.config, clock_dtype)
    prog = build_program(cell, ref, seed)
    for _ in range(iters):
        prog.iteration()
    return dict(ref.traffic), np.asarray(ref.clock, np.float64)


def traffic_of(rt) -> Dict[str, int]:
    return {f.name: int(getattr(rt.traffic, f.name))
            for f in dataclasses.fields(rt.traffic)}


def compare(got_traffic, got_clock, ref_traffic, ref_clock, limits
            ) -> Dict[str, dict]:
    """The compared numbers, each with its limit: the largest gap of a
    traffic field, and the largest gap of a worker's clock relative to
    the latest reference clock."""
    keys = set(got_traffic) | set(ref_traffic)
    t_gap = max(abs(got_traffic.get(k, -1) - ref_traffic.get(k, -1))
                for k in keys)
    if got_clock.shape != ref_clock.shape:
        c_gap = float("inf")
    else:
        scale = max(float(np.max(np.abs(ref_clock))), 1e-300)
        c_gap = float(np.max(np.abs(got_clock - ref_clock))) / scale
    return {"traffic_gap": {"value": t_gap,
                            "limit": limits["traffic_gap"]},
            "clock_gap": {"value": c_gap, "limit": limits["clock_gap"]}}


class Run:
    """One DSM cell's set-up, iterations, layer context and check."""

    span_names = SPAN_NAMES

    def __init__(self, cell, seed: int, events, traced: bool):
        from repro.core import make_runtime
        from repro.dsm import costmodel
        from repro.dsm.session import session
        self.cell, self.seed = cell, seed
        cfg = cell.config
        self.rt = make_runtime(int(cfg["workers"]), backend=cfg["backend"],
                               cost=getattr(costmodel, cfg["cost_model"]),
                               **runtime_kwargs(cfg))
        if self.rt.backend != cfg["backend"]:
            from chipbench.harness import Refused
            raise Refused(f"the runtime resolved tier {self.rt.backend!r}, "
                          f"the configuration states {cfg['backend']!r}")
        self.traced = traced
        self.drv = SpanDriver(session(self.rt, cfg["driver"]), self.rt.alloc)
        self.prog = build_program(cell, self.drv, seed)
        self.iters = 0
        self._stats0: Dict[str, int] = {}
        self.window_stats: Dict[str, int] = {}
        self.window_calls: Optional[List] = None

    def iteration(self):
        self.prog.iteration()
        self.iters += 1

    def program_compiles(self) -> int:
        return int(self.rt.stats.get("jit_cache_misses", 0))

    def start_window(self):
        self._stats0 = dict(self.rt.stats)
        self.drv.calls, self.drv.traced = [], self.traced

    @property
    def calls(self) -> Optional[List]:
        """The window's calls so far, each (name, seconds)."""
        return self.drv.calls if self.drv is not None else None

    def end_window(self):
        self.window_calls, self.drv.calls = self.drv.calls, None
        self.window_stats = {k: v - self._stats0.get(k, 0)
                             for k, v in self.rt.stats.items()
                             if isinstance(v, (int, np.integer))}

    def check_device_path(self):
        from chipbench.harness import Refused
        st = self.window_stats
        if st.get("jit_dispatches", 0) <= 0:
            raise Refused("no protocol kernel ran on the device in the "
                          "window")
        if st.get("jit_flush_fallbacks", 0):
            raise Refused("a barrier flush fell back to the host in the "
                          "window")

    def end_to_end(self, window_s: float, n: int) -> dict:
        return {"iter_ms": window_s / n * 1e3}

    def layer_context(self, tr, peaks, window_s: float, n: int) -> dict:
        pw = int(self.cell.config["page_words"])
        return {"iters": n, "window_s": window_s, "spans": self.window_calls,
                "counters": self.window_stats, "trace": tr, "peaks": peaks,
                "kernel_modules": KERNEL_MODULES,
                "flush_kernel": FLUSH_KERNEL,
                "flush_min_bytes": flops.flush_min_bytes(
                    self.prog.written_cells(pw))}

    def verify(self) -> Dict[str, dict]:
        cfg = self.cell.config
        got_t = traffic_of(self.rt)
        got_c = np.array(self.rt.clock, np.float64)
        iters = self.iters
        self.rt = self.drv = self.prog = None       # free the program
        ref_t, ref_c = replay(self.cell, self.seed, iters)
        return compare(got_t, got_c, ref_t, ref_c, cfg["limits"])


def control_readings(cell, seed: int, result: dict) -> dict:
    """The control against the reference on the same work: the reference
    with its modeled clocks held in float32, the precision below the
    float64 that the configuration's guarantee states."""
    iters = result["iterations"]
    ref_t, ref_c = replay(cell, seed, iters)
    ctl_t, ctl_c = replay(cell, seed, iters, clock_dtype=np.float32)
    return {"control": compare(ctl_t, ctl_c, ref_t, ref_c,
                               cell.config["limits"])}
