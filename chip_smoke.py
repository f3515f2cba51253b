"""Chip smoke test: the RegC coherence runtime's main path on a TPU.

    python chip_smoke.py              # one chip: the protocol phases
    python chip_smoke.py --chips 4    # four chips: the RegC gradient sync only

One chip.  The paper's deployments run through the normal entry points
(``benchmarks.common.make_rt`` -> ``make_runtime``, the ``repro.dsm.apps``
programs over ``Session``) at the committed benchmark's sizes, each on the
'pallas-jit' tier and on the numpy reference tier in this same process:

* ``kernels``        — every protocol kernel at W=256 and a 64k-page
  window (32k for the fused flush), and ``diff_encode`` at 64 pages,
  against the numpy oracles;
* ``jacobi_weak``    — Fig. 6 weak scaling at W=256, n=65536, reduction;
* ``stream_spill``   — Fig. 4 STREAM at 2x the cache, W=256;
* ``stream_refetch`` — the mid-op refetch torture at W=256 (the point
  that runs the eviction rank-select ``take_and_cut`` on the device);
* ``kv_serving``     — the fig8 serving stream at W=256, both series;
* the refetch and spill points again on the per-op 'pallas' tier.

Each device leg must reproduce the numpy leg's traffic field for field
and its clocks bit for bit, must resolve to the backend it asked for,
and on 'pallas-jit' must dispatch ``phase_step`` exactly once per flush
that had a dirty region.  Each device phase runs twice: the cold leg
compiles, the warm leg repeats the same shapes.  Walls are host clock;
every kernel output is read back to the host before the runtime goes on,
which waits for the device, so a wall ends when the device work is done.

Four chips.  ``make_train_step_regc`` on a 4-device ``("data",)`` mesh
with the lazy_object, eager_object and int8_ring policies, against the
GSPMD ``make_train_step`` on the same global batch, for internlm2-1.8b at
its published widths with its depth cut to fit fp32 AdamW replicated in
16 GB.

The script refuses to run — non-zero exit, no result line — when JAX
finds no TPU, when Pallas is unavailable, when ``REPRO_FORCE_NUMPY`` is
set, or outside a checkout of the repository.  Everything runs in this
one process.  The last line of stdout is the result:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
W = 256                          # the paper's largest worker count
SYNC_DEPTH = 2                   # internlm2-1.8b layers kept on 4 chips
SYNC_BATCH, SYNC_SEQ = 16, 512   # global batch: 4 sequences per chip
# how far each RegC policy's synchronized gradient may sit from the GSPMD
# step's, in L2 relative to it, and what share of the fp32 policies'
# parameters may sit more than 1% of the largest update off (``sync_phase``)
GRAD_RTOL = {"lazy_object": 1e-4, "eager_object": 1e-4, "int8_ring": 0.1}
PARAM_FRAC_OFF = {"lazy_object": 1e-4, "eager_object": 1e-4}


def fail(msg: str):
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str):
    if not cond:
        fail(msg)


def preflight(n_chips: int):
    """Refuse before any work unless the device path is what will run."""
    if not (ROOT / "src" / "repro").is_dir() or not (
            ROOT / "benchmarks").is_dir():
        fail(f"no repository checkout around {ROOT} (src/repro and "
             "benchmarks/ must sit next to this script)")
    if "REPRO_FORCE_NUMPY" in os.environ:
        fail("REPRO_FORCE_NUMPY is set: it pins every protocol kernel to "
             "the numpy tier")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"JAX found no TPU (platform {devices[0].platform!r}); this "
             "smoke test runs only on the chip")
    if len(devices) < n_chips:
        fail(f"--chips {n_chips} needs {n_chips} TPU devices, JAX found "
             f"{len(devices)}")
    from repro.kernels.protocol_sweep import HAVE_PALLAS, resolve_backend
    if not HAVE_PALLAS:
        fail("jax.experimental.pallas did not import: no device tier")
    for backend in ("pallas", "pallas-jit"):
        if resolve_backend(backend) != backend:
            fail(f"backend {backend!r} resolves to "
                 f"{resolve_backend(backend)!r}")
    from benchmarks.common import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    return devices


def events_now(events):
    """A ``chipbench.harness.CompileEvents`` reading, for ``since``."""
    return (events.hits, events.misses, events.compiles, events.compile_s)


def since(events, snap):
    h, m, c, s = snap
    return {"cache_hits": events.hits - h,
            "cache_misses": events.misses - m,
            "backend_compiles": events.compiles - c,
            "backend_compile_s": events.compile_s - s}


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def emit(record: dict):
    print("phase " + json.dumps(record, default=float), flush=True)


# ---------------------------------------------------------------------------
# one chip: protocol phases
# ---------------------------------------------------------------------------


def kernel_oracles(device, events):
    """Every protocol kernel on both device tiers against numpy."""
    from repro.kernels import ops
    from repro.kernels import protocol_sweep as ps
    rng = np.random.default_rng(0)
    nw = 2048                                       # 64k-page window
    density = rng.choice([0.0, 1 / 16, 0.5, 15 / 16], size=(W, 1))
    bits = ps.pack_mask_rows(
        rng.random((W, nw * 32), dtype=np.float32) < density)
    total = ps.popcount_rows(bits)
    k = rng.integers(0, total + 2)
    k[1] = 0
    k[2] = total[2]
    delta = rng.permutation(np.r_[np.ones(W), -np.ones(W)]).astype(np.int64)
    want = {"popcount": total, "take_first_k": ps.take_first_k(bits, k),
            "kth_set_index": ps.kth_set_index(bits, k),
            "coverage": ps.coverage_multi(delta)}
    rec = {"phase": "kernels", "W": W, "window_pages": nw * 32}
    snap = events_now(events)
    t0 = time.perf_counter()
    for backend in ("pallas", "pallas-jit"):
        st = {}
        got = {"popcount": ps.popcount_rows(bits, backend=backend, stats=st),
               "take_first_k": ps.take_first_k(bits, k, backend=backend,
                                               stats=st),
               "kth_set_index": ps.kth_set_index(bits, k, backend=backend,
                                                 stats=st),
               "coverage": ps.coverage_multi(delta, backend=backend,
                                             stats=st)}
        take, cut = ps.take_and_cut(bits, k, backend=backend, stats=st)
        got["take_and_cut"] = (take, cut)
        want["take_and_cut"] = (want["take_first_k"], want["kth_set_index"])
        for name, ref in want.items():
            out = got[name]
            same = (all(np.array_equal(a, b) for a, b in zip(out, ref))
                    if isinstance(ref, tuple) else np.array_equal(out, ref))
            check(same, f"kernels: {backend} {name} differs from numpy")
        prefix = "jit_" if backend == "pallas-jit" else "pallas_"
        ran = {k_[len(prefix):] for k_ in st if k_.startswith(prefix)}
        check(ran >= {"popcount", "take_first_k", "kth_set_index",
                      "coverage"},
              f"kernels: {backend} did not run every kernel: {st}")
        rec[f"calls_{backend}"] = st
    # the fused flush chain at R=3 regions of a 32k-page window
    R, nwf = 3, 1024
    fb = rng.integers(0, 1 << 32, (R, W, nwf), dtype=np.uint32)
    fb &= rng.integers(0, 1 << 32, (R, W, nwf), dtype=np.uint32)
    base = np.tile((np.arange(W) * (nwf * 16)).astype(np.int32), (R, 1))
    sb = np.sort(base, axis=1)
    se = np.sort(base + nwf * 32, axis=1).astype(np.int32)
    rowmask = rng.random((R, W)) < 0.9
    st = {}
    counts, shared = ps.phase_step(fb, base, rowmask, sb, se, stats=st)
    c_np, s_np = ps._phase_step_np(fb, base, rowmask, sb, se)
    check(np.array_equal(counts, c_np) and np.array_equal(shared, s_np),
          "kernels: phase_step differs from the numpy oracle")
    check(st.get("jit_phase_step") == 1, f"kernels: phase_step stats {st}")
    # the reference engine's twin diff, compiled, at 64 pages
    import jax.numpy as jnp
    curr = rng.standard_normal((64, 1024)).astype(np.float32)
    twin = curr.copy()
    twin[rng.random((64, 1024)) < 0.1] += 1.0
    mask, vals, count = ops.diff_encode(jnp.asarray(curr), jnp.asarray(twin))
    changed = curr.view(np.int32) != twin.view(np.int32)
    check(np.array_equal(np.asarray(mask) != 0, changed)
          and np.array_equal(np.asarray(count), changed.sum(1))
          and np.array_equal(np.asarray(vals), np.where(changed, curr, 0)),
          "kernels: diff_encode differs from numpy")
    rec["wall_s"] = time.perf_counter() - t0
    rec.update(since(events, snap))
    rec["peak_bytes_in_use"] = peak_bytes(device)
    emit(rec)


def count_dirty_flushes(rt):
    """Count the flushes that must dispatch ``phase_step``: those entered
    with at least one dirty region (the fused chain's own condition,
    evaluated here before the flush runs)."""
    n = {"flushes": 0}
    inner = rt._flush_all_workers

    def flush(mask=None):
        if any(d.maybe_dirty and d.cap > 0 for d in rt.dirs):
            n["flushes"] += 1
        return inner(mask)
    rt._flush_all_workers = flush
    return n


@dataclasses.dataclass
class Phase:
    name: str
    series: str
    rt_kw: dict
    run: object                      # callable(rt) -> report or None


def protocol_phases():
    from benchmarks import kv_serving as kvb
    from benchmarks.stream_triad import (N_BASE, REFETCH_CACHE_PAGES,
                                         REFETCH_WIDTH_PAGES,
                                         REFETCH_WORDS_PER_WORKER,
                                         SPILL_CACHE_PAGES, spill_iters)
    from repro.dsm.apps import jacobi, stream_refetch, stream_triad
    spill = Phase("stream_spill", "samhita",
                  {"cache_pages": SPILL_CACHE_PAGES},
                  lambda rt: stream_triad(rt, 2 * N_BASE * W, spill_iters(8),
                                          driver="batched"))
    refetch = Phase("stream_refetch", "samhita",
                    {"cache_pages": REFETCH_CACHE_PAGES},
                    lambda rt: stream_refetch(
                        rt, REFETCH_WORDS_PER_WORKER * W, 2, sweeps=2,
                        width_pages=REFETCH_WIDTH_PAGES, driver="batched"))
    jit = [Phase("jacobi_weak", "samhita", {},
                 lambda rt: jacobi(rt, 65536, 3, mode="reduction",
                                   driver="batched")),
           spill, refetch]
    for series in ("samhita", "samhita_page"):
        jit.append(Phase(f"kv_serving_{series}", series,
                         {"cache_pages": kvb.CACHE_PAGES},
                         lambda rt: kvb.serve(rt, "batched")))
    return [("pallas-jit", p) for p in jit] + [
        ("pallas", refetch), ("pallas", spill)]


def run_protocol_phase(backend, phase, device, events):
    from benchmarks.common import make_rt, traffic_fields
    ref = make_rt(phase.series, W, backend="numpy", **phase.rt_kw)
    t0 = time.perf_counter()
    ref_rep = phase.run(ref)
    numpy_wall = time.perf_counter() - t0
    rec = {"phase": phase.name, "backend": backend, "W": W,
           "numpy_wall_s": numpy_wall,
           "regions_pages": [int(d.cap) for d in ref.dirs]}
    for leg in ("cold", "warm"):
        rt = make_rt(phase.series, W, backend=backend, **phase.rt_kw)
        check(rt.backend == backend,
              f"{phase.name}: asked for backend {backend!r}, the runtime "
              f"resolved {rt.backend!r}")
        flushes = count_dirty_flushes(rt)
        snap = events_now(events)
        t0 = time.perf_counter()
        rep = phase.run(rt)
        wall = time.perf_counter() - t0
        tag = f"{phase.name}[{backend}, {leg}]"
        check(traffic_fields(rt) == traffic_fields(ref),
              f"{tag}: traffic differs from numpy")
        check(np.array_equal(rt.clock, ref.clock),
              f"{tag}: clocks differ from numpy")
        if rep is not None and hasattr(rep, "latencies"):
            check(np.array_equal(rep.latencies(), ref_rep.latencies()),
                  f"{tag}: request latencies differ from numpy")
        calls = {k: v for k, v in rt.stats.items()
                 if k.startswith(("jit_", "pallas_"))}
        if backend == "pallas-jit":
            check(calls.get("jit_flush_fallbacks", 0) == 0,
                  f"{tag}: the int32 guard sent a flush back to the host")
            check(calls.get("jit_phase_step", 0) == flushes["flushes"],
                  f"{tag}: phase_step dispatched "
                  f"{calls.get('jit_phase_step', 0)} times for "
                  f"{flushes['flushes']} flushes with a dirty region")
            if phase.name == "stream_refetch":
                check(calls.get("jit_take_and_cut", 0) > 0,
                      f"{tag}: take_and_cut never dispatched")
        else:
            check(calls.get("jit_dispatches", 0) == 0
                  and sum(calls.values()) > 0,
                  f"{tag}: no per-op pallas kernel ran: {calls}")
        rec[leg] = {"wall_s": wall, "dirty_flushes": flushes["flushes"],
                    "calls": calls, **since(events, snap)}
    rec["peak_bytes_in_use"] = peak_bytes(device)
    emit(rec)


def one_chip(devices, events):
    kernel_oracles(devices[0], events)
    for backend, phase in protocol_phases():
        run_protocol_phase(backend, phase, devices[0], events)


# ---------------------------------------------------------------------------
# four chips: the RegC gradient sync
# ---------------------------------------------------------------------------


def sync_config():
    """internlm2-1.8b at its published widths, depth cut to SYNC_DEPTH."""
    from repro.configs import get_config
    cfg = get_config("internlm2-1.8b")
    return dataclasses.replace(cfg, name=f"{cfg.name}-{SYNC_DEPTH}L",
                               n_layers=SYNC_DEPTH)


def sync_steps(cfg, mesh):
    """The compared steps, each (tag, jitted fn, n_micro, policy tag):
    the GSPMD reference and the three RegC policies, params and optimizer
    state donated (replicated), batch sharded over ``data``."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.regc_sync.policies import RegCSyncPolicy
    from repro.train.train_step import (TrainHParams, make_train_step,
                                        make_train_step_regc)
    rep = NamedSharding(mesh, P())
    bsh = NamedSharding(mesh, P("data"))
    hp = TrainHParams(remat=None, ce_chunk=128)
    steps = [("gspmd", jax.jit(
        make_train_step(cfg, hp), in_shardings=(rep, rep, bsh, rep),
        out_shardings=(rep, rep, rep), donate_argnums=(0, 1)))]
    for tag, pol in (("lazy_object", RegCSyncPolicy("lazy", "object")),
                     ("eager_object", RegCSyncPolicy("eager", "object")),
                     ("int8_ring", RegCSyncPolicy(
                         "lazy", "object", compression="int8_ring"))):
        hp2 = dataclasses.replace(hp, n_micro=2, sync=pol)
        steps.append((tag, jax.jit(
            make_train_step_regc(cfg, hp2, mesh, dp_axes=("data",)),
            donate_argnums=(0, 1))))
    return steps, rep, bsh


def sync_inputs(cfg, rep, bsh, batch, seq):
    import jax
    import jax.numpy as jnp
    from repro.models import model as M
    from repro.optim.adamw import init_opt_state

    def init(key):
        params = M.init_model_params(cfg, key, jnp.float32)
        return params, init_opt_state(params)
    params, opt = jax.jit(init, out_shardings=rep)(jax.random.PRNGKey(0))
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    data = {"tokens": jax.random.randint(ks[0], (batch, seq), 0,
                                         cfg.vocab_size),
            "targets": jax.random.randint(ks[1], (batch, seq), 0,
                                          cfg.vocab_size)}
    data = jax.device_put(data, bsh)
    return params, opt, data


def host_tree(tree):
    import jax
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def l2_dist(xs, ys):
    return float(np.sqrt(sum(np.sum(np.square(x.astype(np.float64) - y))
                             for x, y in zip(xs, ys))))


def sync_phase(cfg, devices, batch, seq, events, *, step_index=100):
    """Run each compared step once from the same seeded state; check the
    RegC policies against the GSPMD step.  ``step_index`` defaults to the
    end of warmup, where the learning rate is at its peak."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.launch import hlo_analysis
    mesh = Mesh(np.asarray(devices[:4]), ("data",))
    steps, rep, bsh = sync_steps(cfg, mesh)
    step0 = jax.device_put(jnp.asarray(step_index, jnp.int32), rep)
    n_params = None
    results = {}
    p_old = None
    for tag, fn in steps:
        params, opt, data = sync_inputs(cfg, rep, bsh, batch, seq)
        if p_old is None:
            p_old = host_tree(params)
            n_params = sum(x.size for x in p_old)
        snap = events_now(events)
        t0 = time.perf_counter()
        compiled = fn.lower(params, opt, data, step0).compile()
        compile_s = time.perf_counter() - t0
        hlo = hlo_analysis.analyze(compiled.as_text())
        mem = compiled.memory_analysis()
        t0 = time.perf_counter()
        params, opt, metrics = compiled(params, opt, data, step0)
        jax.block_until_ready(params)
        first_s = time.perf_counter() - t0
        p_new, g_new = host_tree(params), host_tree(opt["m"])
        m = {k: float(v) for k, v in metrics.items()}
        t0 = time.perf_counter()
        for _ in range(3):
            params, opt, _m = compiled(params, opt, data, step0)
        jax.block_until_ready(params)
        steady_s = (time.perf_counter() - t0) / 3
        results[tag] = (p_new, g_new, m)
        emit({"phase": f"regc_sync[{tag}]", "chips": 4,
              "config": cfg.name, "n_params": n_params,
              "global_batch": [batch, seq],
              "loss": m["loss"], "grad_norm": m["grad_norm"],
              "collective_bytes_per_dev": hlo.total_collective_bytes,
              "collectives": {k: v for k, v in hlo.collective_count.items()
                              if v},
              "temp_bytes": mem.temp_size_in_bytes,
              "argument_bytes": mem.argument_size_in_bytes,
              "compile_s": compile_s, "first_step_s": first_s,
              "steady_step_s": steady_s,
              "peak_bytes_in_use": peak_bytes(devices[0]),
              **since(events, snap)})
        del params, opt, data, compiled
    ref_p, ref_g, ref_m = results["gspmd"]
    # From fresh moments AdamW moves each element by about +-lr whatever
    # its gradient's size, so an element whose gradient sits at the noise
    # floor may move the other way under another summation order: one
    # element may differ by up to twice the largest update, never more.
    # The fp32 policies may have only a few such elements: PARAM_FRAC_OFF
    # bounds the share more than 1% of the largest update off.  The first
    # moment after one step is 0.1x the synchronized, clipped gradient, so
    # it compares the sync itself: its L2 distance to the GSPMD step's,
    # relative to that, is held to GRAD_RTOL.
    u_max = max(float(np.max(np.abs(a - b))) for a, b in zip(ref_p, p_old))
    g_norm = l2_dist(ref_g, [0.0] * len(ref_g))
    check(u_max > 0, "regc_sync: the reference step changed no parameter")
    summary = {"phase": "regc_sync", "max_ref_update": u_max}
    for tag, (p, g, m) in results.items():
        if tag == "gspmd":
            continue
        rec = {"max_param_diff": max(float(np.max(np.abs(a - b)))
                                     for a, b in zip(p, ref_p)),
               "param_frac_off": sum(
                   int(np.count_nonzero(np.abs(a - b) > 0.01 * u_max))
                   for a, b in zip(p, ref_p)) / n_params,
               "grad_rel_l2": l2_dist(g, ref_g) / g_norm,
               "loss_rel": abs(m["loss"] - ref_m["loss"]) / abs(ref_m["loss"]),
               "grad_norm_rel": abs(m["grad_norm"] - ref_m["grad_norm"])
               / abs(ref_m["grad_norm"])}
        summary[tag] = rec
        fails = [f"loss {m['loss']} vs GSPMD {ref_m['loss']}"
                 ] if rec["loss_rel"] > 2e-4 else []
        if rec["grad_rel_l2"] > GRAD_RTOL[tag]:
            fails.append(f"synced gradient off by {rec['grad_rel_l2']} of "
                         f"its L2 norm > {GRAD_RTOL[tag]}")
        if rec["max_param_diff"] > 2.0 * u_max * (1 + 1e-6):
            fails.append(f"a parameter off by {rec['max_param_diff']}, more "
                         f"than twice the largest update {u_max}")
        if rec["param_frac_off"] > PARAM_FRAC_OFF.get(tag, 1.0):
            fails.append(f"{rec['param_frac_off']} of the parameters more "
                         f"than 1% of the largest update off > "
                         f"{PARAM_FRAC_OFF[tag]}")
        rec["ok"] = not fails
        summary.setdefault("fails", []).extend(
            f"regc_sync[{tag}]: {f}" for f in fails)
    emit(summary)
    check(not summary.get("fails"), "; ".join(summary.get("fails", [])))


def four_chips(devices, events):
    cfg = sync_config()
    print(f"regc_sync: {cfg.name}: d_model {cfg.d_model}, heads "
          f"{cfg.n_heads}/{cfg.n_kv_heads} kv, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}; depth cut 24 -> {cfg.n_layers} layers to fit "
          "fp32 AdamW replicated in 16 GB per chip", flush=True)
    import jax
    # every step multiplies in full fp32 (the TPU's default rounds f32
    # matmul inputs to bf16), so summation order is what the compared
    # steps differ in
    with jax.default_matmul_precision("highest"):
        sync_phase(cfg, devices, SYNC_BATCH, SYNC_SEQ, events)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the RegC gradient sync on a 4-chip "
                         "mesh (default: 1, the protocol phases)")
    args = ap.parse_args(argv)
    devices = preflight(args.chips)
    from chipbench.harness import CompileEvents
    events = CompileEvents()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(devices, events)
    else:
        one_chip(devices, events)
    print(f"total wall {time.perf_counter() - t0:.3f} s; persistent "
          f"compile cache: {events.hits} hits, {events.misses} misses",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
