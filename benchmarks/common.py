"""Shared benchmark plumbing: runtime factory (paper series names), steady-
state timing, CSV + machine-readable JSON emission."""
from __future__ import annotations

import csv
import json
import os
import warnings
from pathlib import Path
from typing import Dict, List, Optional

from repro.core import FINE_PROTO, IDEAL_PROTO, PAGE_PROTO, make_runtime
from repro.core.regc_scale import RegCScaleRuntime
from repro.dsm.costmodel import IB_2013

# paper series -> protocol
SERIES = {
    "pthreads": IDEAL_PROTO,
    "samhita": FINE_PROTO,        # fine-grain consistency-region updates
    "samhita_page": PAGE_PROTO,   # page invalidation everywhere
}

OUT_DIR = Path(os.environ.get("BENCH_OUT", "artifacts/bench"))
REPO_ROOT = Path(__file__).resolve().parents[1]


def enable_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache for every compile of
    this process and return its directory (None without jax).
    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    stands; otherwise the cache lives at the fixed ``<repo>/.jax_cache``
    (the path is part of the cache key, so it never depends on a
    temporary name, PID or time)."""
    try:
        import jax
    except ImportError:
        return None
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # the protocol kernels compile in well under JAX's default 1 s floor
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir


def make_rt(series: str, workers: int, **kw) -> RegCScaleRuntime:
    kw.setdefault("cost", IB_2013)
    kw.setdefault("fetch_batch", 16)   # Samhita's bulk-fetch optimization
    # BENCH_DETECT_RACES=1 flips race detection on for EVERY bench point:
    # the pure-observer check — no committed traffic or modeled-time
    # number may change (benchmarks.compare --strict-model verifies)
    kw.setdefault("detect_races",
                  os.environ.get("BENCH_DETECT_RACES") == "1")
    return make_runtime(workers, protocol=SERIES[series], **kw)


def traffic_fields(rt) -> Dict[str, int]:
    """Exact per-point protocol traffic, flattened for CSV/JSON rows
    (``tr_`` prefix).  ``benchmarks.compare`` diffs these field-for-field
    and fails on ANY mismatch — the exactness regression gate."""
    import dataclasses
    return {f"tr_{f.name}": getattr(rt.traffic, f.name)
            for f in dataclasses.fields(type(rt.traffic))}


def danger_fields(rt) -> Dict[str, int]:
    """Danger-path counters for the spill sections: how many
    danger-flagged ops the vectorized refetch schedule absorbed vs how
    many fell back to the scalar page walk.  Recorded per row so the
    committed results PROVE the vectorized path (not the fallback) ran
    the spill regimes."""
    stats = getattr(rt, "stats", {})
    return {"danger_vec": stats.get("danger_vec_ops", 0),
            "danger_scalar": stats.get("danger_scalar_ops", 0),
            "danger_shared": stats.get("danger_shared_ops", 0)}


def chaos_fields(rt) -> Dict[str, int]:
    """Chaos/straggler counters for the recovery section: message-loss
    ticks, drops, invalidation retransmissions, and barrier straggler
    checks/flags.  Recorded per row (and gated by ``benchmarks.compare``
    like the traffic fields) so the committed results PROVE the
    injection and retry paths fired — no silently-idle chaos."""
    stats = getattr(rt, "stats", {})
    return {"chaos_msgs": stats.get("chaos_msgs", 0),
            "chaos_drops": stats.get("chaos_drops", 0),
            "chaos_inval_retries": stats.get("chaos_inval_retries", 0),
            "straggler_checks": stats.get("straggler_checks", 0),
            "straggler_flags": stats.get("straggler_flags", 0)}


def race_fields(rt) -> Dict[str, int]:
    """Race-detector counters for the fig11 section: distinct flagged
    write/write and read/write page races.  Deterministic (detection is
    exact at page granularity over declared ranges), so gated by
    ``benchmarks.compare`` like the ``danger_*``/``span_*`` counters —
    the committed results PROVE the detector flagged the seeded races,
    not silently idled."""
    stats = getattr(rt, "stats", {})
    return {"race_ww": stats.get("race_ww", 0),
            "race_rw": stats.get("race_rw", 0)}


def span_fields(rt) -> Dict[str, int]:
    """Span-engine path counters for the lock sections: how many span
    bodies the analytic batched group pass absorbed vs how many fell
    back to the per-worker serial body.  Recorded per row (and gated by
    ``benchmarks.compare`` like the danger counters) so the committed
    results PROVE the pipelined path ran the contended regimes."""
    stats = getattr(rt, "stats", {})
    return {"span_vec": stats.get("span_workers_vec", 0),
            "span_serial": stats.get("span_serial_workers", 0)}


def jit_fields(rt_or_stats) -> Dict[str, int]:
    """Fused-dispatch counters for the 'pallas-jit' tier.
    ``jit_dispatches`` (how many fused device programs actually ran) is
    deterministic per point and gated by ``benchmarks.compare`` like the
    traffic fields — a zero on a jit-backed point is the silent
    numpy-fallback signature.  ``jit_cache_misses`` mirrors jax's
    process-wide compile cache (it depends on what ran earlier in the
    process), so it is emitted as un-prefixed ``compiles`` — report-only,
    outside the gate."""
    stats = getattr(rt_or_stats, "stats", rt_or_stats) or {}
    return {"jit_dispatches": stats.get("jit_dispatches", 0),
            "compiles": stats.get("jit_cache_misses", 0)}


class SteadyState:
    """Capture per-iteration modeled time, skipping the cold first iter."""

    def __init__(self):
        self.times: List[float] = []

    def __call__(self, it, rt):
        self.times.append(rt.time)

    def per_iter(self) -> float:
        if not self.times:
            raise ValueError("per_iter(): no iterations recorded")
        if len(self.times) < 3:
            warnings.warn(
                f"per_iter(): only {len(self.times)} iteration(s) recorded; "
                "steady-state estimate degrades to mean of available "
                "(run with --iters >= 3 for a cold-start-free figure)",
                RuntimeWarning, stacklevel=2)
            if len(self.times) == 1:
                return self.times[0]
        return (self.times[-1] - self.times[0]) / (len(self.times) - 1)


def _point_keys(rows) -> set:
    return {(r.get("figure"), r.get("series"), str(r.get("p")))
            for r in rows}


def write_csv(name: str, rows: List[Dict]):
    """Write section rows to ``artifacts/bench/<name>.csv``.

    The committed CSVs are ground truth for the no-drift tests and the
    compare traffic gate, so a *partial* invocation (e.g. a single-figure
    or smoke run) must not clobber a richer artifact: if the existing
    file covers (figure, series, p) points the new rows lack, the rows
    land in ``<name>.partial.csv`` instead, with a printed notice.
    ``BENCH_REFRESH=1`` overrides the guard — the escape hatch for
    deliberate point removals/renames, which would otherwise leave a
    stale key in the committed file forever."""
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    path = OUT_DIR / f"{name}.csv"
    if path.exists() and os.environ.get("BENCH_REFRESH") != "1":
        try:
            with open(path, newline="") as fh:
                old_keys = _point_keys(csv.DictReader(fh))
        except Exception:
            old_keys = set()
        missing = old_keys - _point_keys(rows)
        if missing:
            partial = OUT_DIR / f"{name}.partial.csv"
            print(f"write_csv: {path} covers {len(missing)} point(s) this "
                  f"run lacks; writing {partial} instead (BENCH_REFRESH=1 "
                  "forces a refresh after deliberate point removals)")
            path = partial
    fields: List[str] = []
    for r in rows:                     # union of keys, first-seen order
        for k in r:
            if k not in fields:
                fields.append(k)
    with open(path, "w", newline="") as fh:
        w = csv.DictWriter(fh, fieldnames=fields, restval="")
        w.writeheader()
        w.writerows(rows)
    return path


def print_rows(rows: List[Dict]):
    for r in rows:
        print(",".join(str(v) for v in r.values()), flush=True)
    print()


# ---------------------------------------------------------------------------
# machine-readable results (BENCH_scale.json) for perf-trajectory tracking
# ---------------------------------------------------------------------------


def bench_json_rows(rows: List[Dict]) -> List[Dict]:
    """Normalize section rows to the BENCH_scale.json schema:
    {section, protocol, W, t_wall_s, t_model_s, total_bytes}.  Handles the
    three row shapes the harness produces: protocol sections (figure/
    series/p), regc_training (policy), and roofline (arch/shape/mesh)."""
    out = []
    for r in rows:
        if "series" in r:              # protocol sections
            out.append({
                "section": r["figure"], "protocol": r["series"],
                "W": r["p"], "driver": r.get("driver", "loop"),
                "t_wall_s": r.get("t_wall_s"),
                "t_model_s": r.get("t_model_s", r.get("t_iter_s")),
                "total_bytes": r.get("net_bytes", 0),
                **{k: v for k, v in r.items()
                   if k.startswith("tr_") or k.startswith("danger_")
                   or k.startswith("span_") or k.startswith("chaos_")
                   or k.startswith("straggler_")
                   or k.startswith("rec_") or k.startswith("race_")
                   or k.startswith("srv_") or k.startswith("jit_")}})
        elif "policy" in r:            # regc_training (8-way DP mesh)
            out.append({
                "section": "regc_training", "protocol": r["policy"],
                "W": 8, "t_wall_s": r.get("wall_s_per_step"),
                "t_model_s": None,
                "total_bytes": r.get("collective_bytes_per_dev", 0)})
        elif "mesh" in r:              # roofline (modeled per-cell times)
            devs = 1
            for d in str(r["mesh"]).split("x"):
                devs *= int(d)
            t_model = (r.get("t_compute_ms", 0) + r.get("t_memory_ms", 0)
                       + r.get("t_collective_ms", 0)) / 1e3
            out.append({
                "section": f"roofline_{r.get('variant', '?')}",
                "protocol": f"{r.get('arch', '?')}:{r.get('shape', '?')}",
                "W": devs, "t_wall_s": None,
                "t_model_s": round(t_model, 6), "total_bytes": 0})
        else:
            out.append({"section": "?", "protocol": "?", "W": 0,
                        "t_wall_s": None, "t_model_s": None,
                        "total_bytes": 0, "raw": r})
    return out


def write_bench_json(path, rows: List[Dict],
                     meta: Optional[Dict] = None) -> Path:
    p = Path(path)
    if str(p.parent) not in ("", "."):
        p.parent.mkdir(parents=True, exist_ok=True)
    payload = {"meta": meta or {}, "rows": bench_json_rows(rows)}
    p.write_text(json.dumps(payload, indent=1) + "\n")
    return p
