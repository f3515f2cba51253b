"""STREAM TRIAD — paper Figs. 2 (strong), 3 (weak), 4 (cache spill).

Metric: sustained bandwidth GB/s = 3n * 4 bytes / modeled-seconds-per-iter.
The paper runs 400 iterations with a barrier each; per-iteration traffic is
steady after the cold start, so we run fewer and report the steady-state
per-iteration time (asserted steady in tests/test_paper_claims.py).
"""
from __future__ import annotations

import argparse
import time

from benchmarks.common import (SERIES, SteadyState, danger_fields, make_rt,
                               print_rows, traffic_fields, write_bench_json,
                               write_csv)
from repro.dsm.apps import (stream_refetch, stream_spill, stream_triad,
                            triad_bytes_per_iter)

N_BASE = 16 << 20          # paper: n = 16M doubles-worth of fp32 words
CORES = (1, 2, 4, 8, 16, 32, 64, 128, 256)
# Fig-4 cache size: fits the small problem, spills at 2x (also imported
# by the tests that re-derive committed CSV points)
SPILL_CACHE_PAGES = 3 * (N_BASE // 1024) + 64
# mid-op refetch torture: 128-page blocks per worker, 8-page sliding
# windows, a cache of ~1.2 of the 16-page read+write window pair
REFETCH_WORDS_PER_WORKER = 1 << 17
REFETCH_WIDTH_PAGES = 8
REFETCH_CACHE_PAGES = 20


def spill_iters(iters: int) -> int:
    """Iteration rule for the spill section (shared with the no-drift
    tests so re-derivation always matches the harness)."""
    return max(4, iters // 2)


def bw_gbs(n: int, t_iter: float) -> float:
    return triad_bytes_per_iter(n) / t_iter / 1e9


def _point(figure: str, series: str, p: int, n: int, iters: int,
           driver: str = "batched", **rt_kw):
    ss = SteadyState()
    t0 = time.perf_counter()
    rt = make_rt(series if series in SERIES else "samhita", p, **rt_kw)
    stream_triad(rt, n, iters, driver=driver, on_iter=ss)
    t_wall = time.perf_counter() - t0
    return {"figure": figure, "series": series, "p": p, "n": n,
            "driver": driver,
            "t_iter_s": round(ss.per_iter(), 6),
            "bandwidth_GBs": round(bw_gbs(n, ss.per_iter()), 3),
            "net_bytes": rt.traffic.total_bytes,
            "t_model_s": round(rt.time, 6),
            "t_wall_s": round(t_wall, 4),
            **traffic_fields(rt)}


def strong(iters: int, driver: str):
    rows = []
    for p in CORES:
        for series in SERIES:
            if series == "pthreads" and p > 8:
                continue       # Pthreads exists only within one node
            rows.append(_point("fig2_strong", series, p, N_BASE, iters,
                               driver))
    return rows


def weak(iters: int, driver: str):
    rows = []
    for p in CORES:
        n = N_BASE * p
        for series in SERIES:
            if series == "pthreads" and p > 8:
                continue
            rows.append(_point("fig3_weak", series, p, n, iters, driver))
    return rows


def spill(iters: int, driver: str):
    """samhita only: per-worker problem 2x the local cache (Fig 4)."""
    rows = []
    cache_pages = SPILL_CACHE_PAGES
    for p in CORES:
        for scale, tag in ((1, "fits"), (2, "spills")):
            n = N_BASE * p * scale
            r = _point("fig4_spill", f"samhita_{tag}", p, n, iters, driver,
                       cache_pages=cache_pages)
            rows.append(r)
    rows += spill_heavy(iters, driver)
    return rows


def spill_heavy(iters: int, driver: str):
    """Rotating-block spill (``apps.stream_spill``): every pass shifts the
    block assignment, so each worker's dirty block lands inside its
    neighbours' reach — the batched driver's window-disjointness analysis
    routes the interacting workers through tick-ordered residual replay,
    whose per-worker ops hit the danger screen and resolve through the
    vectorized refetch schedule.  Plus the mid-op refetch torture
    (``apps.stream_refetch``): disjoint blocks with half-overlapping
    sliding windows, where EVERY op is danger-flagged and stays on the
    batched path.  Traffic stays bit-identical across drivers; the rows
    record the danger-path counters proving the vectorized schedule (not
    the scalar fallback) absorbed the pattern."""
    rows = []
    for p in (16, 64, 256):
        n = (1 << 17) * p              # 128 pages per worker
        cache_pages = (3 * (n // 1024)) // (2 * p)   # ~¾ of the 2-array set
        ss = SteadyState()
        t0 = time.perf_counter()
        rt = make_rt("samhita", p, cache_pages=cache_pages)
        stream_spill(rt, n, max(2, iters // 2), sweeps=2, driver=driver,
                     on_iter=ss)
        rows.append({"figure": "fig4_spill_heavy", "series": "samhita_rot",
                     "p": p, "n": n, "driver": driver,
                     "t_iter_s": round(ss.per_iter(), 6),
                     "net_bytes": rt.traffic.total_bytes,
                     "t_model_s": round(rt.time, 6),
                     "t_wall_s": round(time.perf_counter() - t0, 4),
                     **traffic_fields(rt), **danger_fields(rt)})
    for p in (16, 64, 256):
        n = REFETCH_WORDS_PER_WORKER * p
        ss = SteadyState()
        t0 = time.perf_counter()
        rt = make_rt("samhita", p, cache_pages=REFETCH_CACHE_PAGES)
        stream_refetch(rt, n, max(2, iters // 2), sweeps=2,
                       width_pages=REFETCH_WIDTH_PAGES, driver=driver,
                       on_iter=ss)
        rows.append({"figure": "fig4_refetch", "series": "samhita_refetch",
                     "p": p, "n": n, "driver": driver,
                     "t_iter_s": round(ss.per_iter(), 6),
                     "net_bytes": rt.traffic.total_bytes,
                     "t_model_s": round(rt.time, 6),
                     "t_wall_s": round(time.perf_counter() - t0, 4),
                     **traffic_fields(rt), **danger_fields(rt)})
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--weak", action="store_true")
    ap.add_argument("--spill", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--driver", choices=["loop", "batched"],
                    default="batched",
                    help="SPMD phase driver: per-worker loop or phase_all")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="also write machine-readable rows here")
    args = ap.parse_args(argv)
    rows = []
    if args.all or not (args.weak or args.spill):
        rows += strong(args.iters, args.driver)
    if args.all or args.weak:
        rows += weak(args.iters, args.driver)
    if args.all or args.spill:
        rows += spill(spill_iters(args.iters), args.driver)
    # non-default drivers get their own CSV so `--driver both` harness
    # runs don't overwrite the batched rows
    write_csv("stream_triad" if args.driver == "batched"
              else f"stream_triad_{args.driver}", rows)
    if args.json:
        write_bench_json(args.json, rows)
    print_rows(rows)
    return rows


if __name__ == "__main__":
    main()
