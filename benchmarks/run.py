"""Benchmark orchestrator — one section per paper figure/table plus the
framework-level benches.  ``python -m benchmarks.run [--fast] [--json OUT]``.

Each section is wall-clock timed and failure-isolated (a section that
cannot run in this container — e.g. a jax-version mismatch — is recorded
as an error instead of aborting the harness), and the combined results are
written to a machine-readable ``BENCH_scale.json`` so future changes can
track the perf trajectory: per-point modeled time + exact traffic, per-
section wall seconds.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from pathlib import Path


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="fewer iterations / skip the slowest sections")
    ap.add_argument("--driver", choices=["loop", "batched", "both"],
                    default="batched",
                    help="SPMD phase driver for the protocol sections: "
                         "per-worker loop, worker-axis-batched phase_all, "
                         "or both (one timed pass per driver)")
    ap.add_argument("--sections", nargs="+", default=None, metavar="SUBSTR",
                    help="run only sections whose name or tags contain any "
                         "of these substrings; 'spill' focuses the protocol "
                         "sections on their capacity-pressure figures (the "
                         "CI bench-smoke subset)")
    ap.add_argument("--json", default="BENCH_scale.json", metavar="OUT",
                    help="write machine-readable results here "
                         "('' disables; default: %(default)s)")
    args = ap.parse_args(argv)
    iters = 4 if args.fast else 8
    drivers = (["loop", "batched"] if args.driver == "both"
               else [args.driver])
    # substring section filter; the 'spill' tag additionally swaps the
    # protocol sections' argv for their spill-only figure subsets, so a
    # focused CI run stays seconds while still crossing the exact-traffic
    # gate (partial runs land in *.partial.csv — the clobber guard)
    focus_spill = bool(args.sections) and any(
        "spill" in s for s in args.sections)

    def keep(name, tags=()):
        return args.sections is None or any(
            s in name or any(s in t for t in tags) for s in args.sections)

    from benchmarks import (availability, common, jacobi, kernels,
                            kv_serving, lock_contention,
                            molecular_dynamics, races, recovery,
                            regc_training, roofline, stream_triad)
    common.enable_compile_cache()

    sections = []
    for d in drivers:
        tag = f"[{d}]" if len(drivers) > 1 else ""
        drv = ["--driver", d]
        st_args = ["--spill"] if focus_spill else ["--all"]
        ja_args = ["--spill"] if focus_spill else ["--all"]
        md_args = ["--spill"] if focus_spill else []
        sections += [
            (f"stream_triad (paper Figs. 2/3/4) {tag}",
             f"stream_triad{tag}", False, ("spill",),
             lambda drv=drv, a=st_args: stream_triad.main(
                 a + ["--iters", str(iters)] + drv)),
            (f"Jacobi (paper Figs. 5/6) {tag}", f"jacobi{tag}", False,
             ("spill",),
             lambda drv=drv, a=ja_args: jacobi.main(
                 a + ["--iters", str(iters)] + drv)),
            (f"Molecular dynamics (paper Fig. 7) {tag}",
             f"molecular_dynamics{tag}", False, ("spill",),
             lambda drv=drv, a=md_args: molecular_dynamics.main(
                 a + ["--iters", str(max(4, iters // 2))] + drv)),
            # a lock-focused run regenerates the exact committed point
            # set, so its CSVs would clobber the committed artifacts
            # (identical keys defeat write_csv's partial routing); the
            # CI bench_lock job redirects them with BENCH_OUT instead,
            # keeping ALL points under the compare gate
            (f"Lock contention (span engine) {tag}",
             f"lock_contention{tag}", False, ("lock",),
             lambda drv=drv: lock_contention.main(
                 ["--iters", str(iters)] + drv)),
            # like lock_contention, a focused run regenerates the exact
            # committed point set — the CI chaos job redirects its CSVs
            # with BENCH_OUT (see bench_lock)
            (f"Crash recovery (checkpoint/replay) {tag}",
             f"recovery{tag}", False, ("chaos",),
             lambda drv=drv: recovery.main(
                 ["--iters", str(max(3, iters // 2))] + drv)),
            # sharded multi-process runtime under injected shard death;
            # like recovery, a focused run regenerates the exact
            # committed point set — the CI cluster job redirects its
            # CSVs with BENCH_OUT (see bench_lock)
            (f"Availability (sharded cluster, process faults) {tag}",
             f"availability{tag}", False, ("cluster",),
             lambda drv=drv: availability.main(
                 ["--iters", str(max(3, iters // 2))] + drv)),
            # detector on/off overhead + pure-observer assertion; like
            # lock_contention, a focused run regenerates the exact
            # committed point set — the CI race job redirects its CSVs
            # with BENCH_OUT (see bench_lock)
            (f"Race detection (detector on/off) {tag}",
             f"races{tag}", False, ("race",),
             lambda drv=drv: races.main(
                 ["--iters", str(iters)] + drv)),
            # KV-cache serving adversary (inference traffic); the request
            # stream is a pure function of (W, seed) — independent of
            # --iters — so like lock_contention a focused run regenerates
            # the exact committed point set and the CI serve job
            # redirects its CSVs with BENCH_OUT (see bench_lock)
            (f"KV-cache serving (inference traffic) {tag}",
             f"kv_serving{tag}", False, ("serve",),
             lambda drv=drv: kv_serving.main(
                 ["--iters", str(iters)] + drv)),
        ]
    sections += [
        # protocol-kernel tiers head-to-head (fig12) + the one-dispatch-
        # per-phase protocol point; driver-independent, so it runs once.
        # A focused run regenerates the exact committed point set — the
        # CI kernels job redirects its CSV with BENCH_OUT (see bench_lock)
        ("Protocol kernels (numpy / pallas / pallas-jit tiers)",
         "kernels", False, ("kernels",),
         lambda: kernels.main(["--iters", str(iters)])),
        # jax-compile-bound (subprocess trainer), not a protocol section
        ("RegC training-layer sync policies (DESIGN.md 2.2)",
         "regc_training", True, (), lambda: regc_training.main([])),
        ("Roofline summary (from dry-run artifacts)", "roofline", False,
         (), lambda: roofline.main(["--mesh", "16x16"])),
    ]

    t0 = time.time()
    all_rows = []
    section_meta = {}
    failed = []
    for title, name, slow, tags, fn in sections:
        if not keep(name, tags):
            continue
        if slow and args.fast:
            print(f"== {title} == (skipped: --fast)", flush=True)
            section_meta[name] = {"wall_s": 0.0, "status": "skipped (--fast)"}
            continue
        print(f"== {title} ==", flush=True)
        s0 = time.time()
        try:
            rows = fn() or []
            status = "ok" if rows else "no data"
        except Exception as e:
            rows = []
            status = f"error: {type(e).__name__}: {e}"
            print(f"section {name} failed: {status}", flush=True)
            traceback.print_exc()
            failed.append(name)
        section_meta[name] = {"wall_s": round(time.time() - s0, 2),
                              "status": status}
        all_rows += rows

    total = time.time() - t0
    print(f"total bench time: {total:.1f}s")
    if args.json:
        prev = None
        if Path(args.json).exists():
            try:
                prev = json.loads(Path(args.json).read_text())
            except Exception:
                prev = None
        out_json = args.json
        if prev is not None:
            # same clobber guard as write_csv, gated purely on coverage:
            # any run missing points the existing file holds (a
            # --sections filter, a failure-isolated section, a --fast run
            # over a full-run baseline) must not replace the compare
            # gate's ground truth (BENCH_REFRESH=1 overrides for
            # deliberate removals)
            def keys(rows):
                return {(r.get("section"), r.get("protocol"), r.get("W"),
                         r.get("driver", "loop")) for r in rows}
            missing = (keys(prev.get("rows", []))
                       - keys(common.bench_json_rows(all_rows)))
            if missing and os.environ.get("BENCH_REFRESH") != "1":
                out_json = str(Path(args.json).with_suffix(".partial.json"))
                print(f"run: {args.json} covers {len(missing)} point(s) "
                      f"this partial run lacks; writing {out_json} "
                      "instead (BENCH_REFRESH=1 forces a refresh)")
        path = common.write_bench_json(
            out_json, all_rows,
            meta={"fast": bool(args.fast), "iters": iters,
                  "driver": args.driver,
                  "sections_filter": args.sections,
                  "total_wall_s": round(total, 2),
                  "sections": section_meta})
        print(f"wrote {path}")
        if args.fast and prev is not None and args.sections is None:
            # smoke-run the regression differ against the previous results
            # (report-only here; CI gates via `python -m benchmarks.compare`)
            from benchmarks import compare
            cur = json.loads(Path(path).read_text())
            print("== compare vs previous BENCH_scale.json ==")
            compare.report(prev, cur)
            # focused pass over the spill sections: the capacity-pressure
            # points are where batched eviction must stay traffic-exact
            print("== compare --sections spill ==")
            compare.report(prev, cur, sections=["spill"])
    if failed:
        # a failure-isolated section must still fail the invocation, or a
        # green-looking run can mask a dead section (the CI regression gate
        # would silently compare nothing for it)
        print(f"FAILED section(s): {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)
    return all_rows


if __name__ == "__main__":
    main()
