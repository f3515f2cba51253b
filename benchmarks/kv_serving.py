"""KV-cache serving — the inference-traffic adversary (fig8_kv_serving).

``apps.kv_serving`` models a continuous-batching inference fleet as a
RegC program: workers are decode slots over a paged KV arena (6-page
slots: 96 KV rows x 64 words on 1024-word pages), a Zipf-skewed
multi-tenant request stream with bursty arrivals (burst size scales with
W so queueing pressure survives the core sweep), admission spans on one
hot lock, bulk prefill writes, and windowed decode reads + appended rows
— under a ``cache_pages`` budget (4) below a cold tenant's prompt
working set, so paged-attention eviction pressure drives the danger and
batched-eviction engine paths (asserted per point below).

Every point reports request-level p50/p99 latency and tokens/s — both
derived from MODELED clocks, so they are deterministic and bit-equal
across drivers/backends, but like ``t_model_s`` they are report-only
perf trajectory, NOT gated.  The gated fields are the exact ``tr_*``
traffic, the ``danger_*``/``span_*`` path counters, and the integer
``srv_*`` workload counters; ``benchmarks.compare`` diffs all of them
field-for-field.  When jax is present a ``pallas``-backend twin runs
in-bench, asserted traffic- AND clock-bit-equal: one live sample per
series by default (batched, W=16 — interpret-mode kernels cost minutes
per point on CPU), the full grid under ``BENCH_PALLAS_TWIN=1`` (run
once when the committed artifacts were produced).  The both-drivers
half of the contract is the committed loop/batched row pairs plus
``tests/test_kv_serving.py``.

The request stream is a pure function of (W, seed), NOT of ``--iters``
(accepted for harness uniformity), so every invocation regenerates the
identical committed point set — like the lock/recovery sections, a
focused run's CSVs are redirected by the CI serve job via ``BENCH_OUT``.
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np

from benchmarks.common import (danger_fields, make_rt, print_rows,
                               span_fields, traffic_fields,
                               write_bench_json, write_csv)
from repro.dsm.apps import kv_serving

CORES = (16, 64, 256)
REQ_PER_SLOT = 3
TOK_WORDS = 64          # one KV row (all layers' K+V for one token)
MAX_TOKENS = 96         # slot capacity -> 6 pages per slot
ATTN_WINDOW = 32        # trailing-window attention reads
CACHE_PAGES = 4         # below a cold prompt's pages: eviction regime
N_TENANTS = 16
SEED = 7


def serve(rt, driver: str):
    """Run the fig8 request stream for ``rt.W`` decode slots on ``rt``."""
    p = rt.W
    return kv_serving(rt, REQ_PER_SLOT * p, tok_words=TOK_WORDS,
                      max_tokens=MAX_TOKENS, attn_window=ATTN_WINDOW,
                      n_tenants=N_TENANTS, burst_mean=max(2, p // 8),
                      gap_max=2, seed=SEED, driver=driver)


def serve_point(series: str, p: int, driver: str, *, backend="numpy"):
    rt = make_rt(series, p, cache_pages=CACHE_PAGES, backend=backend)
    t0 = time.perf_counter()
    rep = serve(rt, driver)
    return rt, rep, time.perf_counter() - t0


def serving(iters: int, driver: str, cores=CORES):
    from repro.kernels.protocol_sweep import HAVE_PALLAS
    rows = []
    for p in cores:
        for series in ("samhita", "samhita_page"):
            rt, rep, wall = serve_point(series, p, driver)
            # paged-attention pressure must actually fire, per point:
            # wide prefills cross the mid-op danger screen on the
            # vectorized path, and (batched driver) the sliding decode
            # windows keep batched eviction rounds live
            assert rt.stats["danger_vec_ops"] > 0, (series, p, driver)
            assert rt.stats["danger_scalar_ops"] == 0, (series, p, driver)
            assert rt.stats["span_all_calls"] > 0 or driver == "loop", \
                (series, p)
            if driver == "batched":
                assert rt.stats["evict_batch_rounds"] > 0, (series, p)
            if HAVE_PALLAS and (os.environ.get("BENCH_PALLAS_TWIN") == "1"
                                or (driver == "batched" and p == 16)):
                # both-backends half of the exactness contract, in-bench.
                # Interpret-mode kernels cost 24-130s per twin on CPU, so
                # the default run pins one live twin per series (batched,
                # W=16) and BENCH_PALLAS_TWIN=1 sweeps the full grid (all
                # points validated once when the artifacts were
                # committed); tests/test_kv_serving.py holds the
                # backend contract at app scale on every CI run.
                rt2, rep2, _ = serve_point(series, p, driver,
                                           backend="pallas")
                assert traffic_fields(rt2) == traffic_fields(rt), \
                    (series, p, driver, "pallas traffic drift")
                np.testing.assert_array_equal(
                    rt2.clock, rt.clock,
                    err_msg=f"pallas clock drift {series} W={p}")
                np.testing.assert_array_equal(rep2.latencies(),
                                              rep.latencies())
                # jit twin on the same live sample: the fused flush
                # chain must reproduce traffic/clocks/latencies exactly
                # AND actually dispatch — jit_dispatches == 0 would mean
                # the compiled tier silently degraded to numpy
                rt3, rep3, _ = serve_point(series, p, driver,
                                           backend="pallas-jit")
                assert traffic_fields(rt3) == traffic_fields(rt), \
                    (series, p, driver, "pallas-jit traffic drift")
                np.testing.assert_array_equal(
                    rt3.clock, rt.clock,
                    err_msg=f"pallas-jit clock drift {series} W={p}")
                np.testing.assert_array_equal(rep3.latencies(),
                                              rep.latencies())
                assert rt3.stats["jit_dispatches"] > 0, \
                    (series, p, driver, "jit twin never dispatched")
            lat = rep.latencies()
            rows.append({
                "figure": "fig8_kv_serving", "series": series, "p": p,
                "n": len(rep.requests), "driver": driver,
                "t_model_s": round(rt.time, 6),
                "t_wall_s": round(wall, 4),
                "net_bytes": rt.traffic.total_bytes,
                # request-level serving metrics (modeled, report-only)
                "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 6),
                "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 6),
                "tokens_per_s": round(rep.tokens_per_s(), 1),
                "req_per_s": round(len(lat) / rep.span_time, 1),
                # gated integer workload counters
                "srv_requests": len(lat),
                "srv_prefill_tok": rep.prefill_tokens,
                "srv_decode_tok": rep.decode_tokens,
                "srv_steps": rep.steps,
                "srv_admit_spans": rep.admit_spans,
                "srv_admitted": rep.admitted,
                "srv_idle_slot_steps": rep.idle_slot_steps,
                "srv_peak_queue": rep.peak_queue,
                "srv_evict_rounds": rt.stats["evict_batch_rounds"],
                **traffic_fields(rt), **danger_fields(rt),
                **span_fields(rt)})
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=8,
                    help="accepted for harness uniformity; the request "
                         "stream is fixed per (W, seed) so the committed "
                         "point set never depends on it")
    ap.add_argument("--driver", choices=["loop", "batched"],
                    default="batched",
                    help="SPMD phase + span driver: per-worker loop or "
                         "phase_all/span_all")
    ap.add_argument("--smoke", action="store_true",
                    help="quick local subset (W <= 64).  Missing the "
                         "committed W=256 keys routes the output to "
                         "*.partial.csv, so the committed artifacts stay "
                         "untouched")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="also write machine-readable rows here")
    args = ap.parse_args(argv)
    rows = serving(args.iters, args.driver,
                   cores=CORES[:2] if args.smoke else CORES)
    write_csv("kv_serving" if args.driver == "batched"
              else f"kv_serving_{args.driver}", rows)
    if args.json:
        write_bench_json(args.json, rows)
    print_rows(rows)
    return rows


if __name__ == "__main__":
    main()
