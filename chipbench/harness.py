"""The benchmark harness: one run of one cell.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name: the cell in
``BENCHMARK.json``, its configuration file, its traffic file
``chipbench/traffic/<traffic>.json`` (read by the general generator of
its configuration's ``kind``, ``chipbench/kinds/<kind>.py``), the user
program that a DSM traffic file names, ``chipbench/programs/<program>.py``,
and each per-layer metric's reader ``chipbench/layers/<metric>.py``.

A run loads, warms up until an iteration compiles nothing, measures for
``--seconds``, checks what the window produced against the benchmark's
own reference, and prints one JSON line last on stdout.  With
``--trace 1`` the window runs under the profiler, with the harness's
spans on the trace's clock, and the line carries the per-layer metrics
and a ``breakdown``.  It refuses -- non-zero exit, no result line --
where JAX finds no TPU or fewer chips than the cell asks for, where the
program's checkout is missing, where the runtime resolves another tier
than the configuration states, where the window compiled, or where no
protocol kernel ran on the device in the window.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".chipbench_trace"


class Refused(Exception):
    """The run cannot measure what the cell asks for."""


def log(msg: str):
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the cell, found by name
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path = ROOT


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    return cell in metric.get("workloads", [cell]) and (
        "moves" not in metric or metric["moves"] in e2e_names)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json "
                      f"(have {sorted(cells)})")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "chipbench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, name, e2e_names)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer,
                root)


def load_file(kind: str, name: str, root: Path = ROOT):
    """The module ``chipbench/<kind>/<name>.py``, loaded by its path."""
    path = root / "chipbench" / kind / f"{name}.py"
    if not path.is_file():
        raise Refused(f"no file chipbench/{kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layer_reader(metric: str, root: Path = ROOT) -> Callable:
    return load_file("layers", metric, root).read


def peaks_for(kind: str, root: Path = ROOT) -> dict:
    table = json.loads((root / "chipbench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise Refused(f"device kind {kind!r} is not in chipbench/peaks.json")
    return table["devices"][kind]


# ---------------------------------------------------------------------------
# JAX: compile cache, compile events, devices
# ---------------------------------------------------------------------------


def enable_compile_cache() -> str:
    """Copied from ``benchmarks.common.enable_compile_cache`` (c6212da):
    ``JAX_COMPILATION_CACHE_DIR`` stands where it is set; otherwise the
    cache lives at the fixed ``<checkout>/.jax_cache``."""
    import jax
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir


class CompileEvents:
    """Copied from ``chip_smoke.CompileEvents`` (c6212da): JAX's own
    compile and persistent-cache events, process-wide."""

    def __init__(self):
        import jax
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        self.hits = self.misses = self.compiles = 0
        self.compile_s = 0.0
        self._compile_event = BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name, secs, **_):
        if name == self._compile_event:
            self.compiles += 1
            self.compile_s += secs

    def count(self) -> int:
        """Every event that means a program was built or loaded."""
        return self.hits + self.misses + self.compiles


def devices_for(chips: int, require_chip: bool):
    import jax
    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            raise Refused(f"JAX found no TPU (platform "
                          f"{devices[0].platform!r})")
        if len(devices) < chips:
            raise Refused(f"the cell needs {chips} chips, JAX found "
                          f"{len(devices)}")
    return devices


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             config_overrides: Optional[dict] = None,
             root: Path = ROOT) -> dict:
    """Set up, warm up, measure, check; return the result object."""
    cell = load_cell(name, root)
    if config_overrides:
        cell.config.update(config_overrides)
    if not (root / "src" / "repro").is_dir():
        raise Refused(f"no program checkout around {root} (src/repro)")
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    import jax
    devices = devices_for(cell.chips, require_chip)
    cache = enable_compile_cache()
    log(f"{name}: seed {seed}, {seconds} s window, trace {int(trace)}, "
        f"compile cache {cache}")
    events = CompileEvents()
    kind = importlib.import_module(f"chipbench.kinds.{cell.config['kind']}")
    run = kind.Run(cell, seed, events, traced=trace)

    # warm up: until an iteration builds or loads no program, and for at
    # least the configuration's ``warmup_min`` iterations
    n_warm, warm_max = 0, int(cell.config.get("warmup_max", 4))
    warm_min = int(cell.config.get("warmup_min", 1))
    while True:
        before = (events.count(), run.program_compiles())
        run.iteration()
        n_warm += 1
        quiet = before == (events.count(), run.program_compiles())
        if (quiet and n_warm >= warm_min) or n_warm >= warm_max:
            break
    if not quiet:
        raise Refused(f"warm-up still compiled after {n_warm} iterations")
    run.start_window()
    before_window = (events.count(), run.program_compiles())
    setup_s = time.perf_counter() - t_start
    log(f"{name}: set-up {setup_s:.3f} s with {n_warm} warm-up "
        f"iterations; compile events {events.count()} "
        f"({events.hits} cache hits, {events.misses} misses, "
        f"{events.compiles} backend compiles, {events.compile_s:.3f} s)")

    if trace:
        import jax.profiler
        shutil.rmtree(TRACE_DIR / name, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR / name),
                                 profiler_options=opts)
    gc_pauses = GcPauses()
    usage = [_usage()]
    stamps = [time.perf_counter()]
    marks = [0]
    with jax.profiler.TraceAnnotation("bench.window") if trace else \
            _nothing():
        while True:
            run.iteration()
            stamps.append(time.perf_counter())
            usage.append(_usage())
            marks.append(len(getattr(run, "calls", None) or ()))
            if stamps[-1] - stamps[0] >= seconds:
                break
    gc_pauses.stop()
    n, t0, t_end = len(stamps) - 1, stamps[0], stamps[-1]
    if trace:
        jax.profiler.stop_trace()
    window_s = t_end - t0
    compiled = (events.count(), run.program_compiles()) != before_window
    log(f"{name}: window {window_s:.6f} s, {n} iterations, compile "
        f"events in the window: {events.count() - before_window[0]}, "
        f"new program shapes: {run.program_compiles() - before_window[1]}")
    steps = sorted(b - a for a, b in zip(stamps, stamps[1:]))
    log(f"{name}: iteration s min {steps[0]:.6f} median "
        f"{steps[len(steps) // 2]:.6f} max {steps[-1]:.6f}; full garbage "
        f"collections in the window: {gc_pauses.count}, "
        f"{gc_pauses.seconds:.6f} s")
    _log_slowest(name, stamps, usage, marks, getattr(run, "calls", None))
    if compiled:
        raise Refused("a program compiled inside the measured window")
    run.end_window()
    run.check_device_path()
    peak = memory_peak(devices[:cell.chips])

    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": peak}
    metrics: Dict[str, dict] = {}
    breakdown = None
    if trace:
        from chipbench import trace as trace_mod
        tr = trace_mod.load(trace_mod.find_xplane(str(TRACE_DIR / name)),
                            run.span_names)
        dev["busy_s"] = tr.busy_s()
        dev["window_s"] = tr.window_s
        try:
            peaks = peaks_for(dev["kind"], root)
        except Refused:
            if require_chip:
                raise
            peaks = None
        ctx = run.layer_context(tr, peaks, window_s, n)
        for m in cell.per_layer:
            v = layer_reader(m["name"], root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": tr.top_ops(10),
                     "idle_gaps": tr.idle_gaps(10)}
    else:
        e2e = {"setup_s": setup_s, **run.end_to_end(window_s, n)}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    # the check: the program's state is read and freed, then the
    # reference replays the same work
    checks = run.verify()
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct, "attempted": n,
              "failed": 0 if correct else n, "metrics": metrics,
              "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["iterations"] = run.iters        # warm-up and window
    result["checks"] = checks
    return result


def _usage():
    """The process's CPU seconds, minor and major page faults, and
    involuntary and voluntary context switches so far."""
    import resource
    r = resource.getrusage(resource.RUSAGE_SELF)
    return (r.ru_utime + r.ru_stime, r.ru_minflt, r.ru_majflt, r.ru_nivcsw,
            r.ru_nvcsw)


def _log_slowest(name, stamps, usage, marks, calls):
    """Log the slowest and the median window iteration side by side: wall,
    the process's CPU time, page faults, context switches and each call's
    time, a diagnostic for far-off iterations."""
    walls = [b - a for a, b in zip(stamps, stamps[1:])]
    order = sorted(range(len(walls)), key=walls.__getitem__)
    for what, i in (("slowest", order[-1]), ("median", order[len(order) // 2])):
        d = [b - a for a, b in zip(usage[i], usage[i + 1])]
        part = ""
        if calls:
            part = "; calls " + ", ".join(
                f"{n} {t:.6f}" for n, t in calls[marks[i]:marks[i + 1]])
        log(f"{name}: {what} iteration {i}: wall {walls[i]:.6f} s, cpu "
            f"{d[0]:.6f} s, minor faults {d[1]}, major faults {d[2]}, "
            f"involuntary switches {d[3]}, voluntary {d[4]}{part}")


class GcPauses:
    """Count and time the interpreter's full (generation 2) collections
    until ``stop``: a diagnostic for far-off iterations."""

    def __init__(self):
        import gc
        self.count, self.seconds, self._t = 0, 0.0, None
        self._gc = gc
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.count += 1
            self.seconds += time.perf_counter() - self._t

    def stop(self):
        self._gc.callbacks.remove(self._cb)


class _nothing:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=t_start)
    except Refused as e:
        log(f"refused: {e}")
        return 2
    for k, c in res["checks"].items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
    return 0
