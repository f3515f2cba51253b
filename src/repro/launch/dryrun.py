import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

# NOTE: the lines above MUST run before any other import (including jax
# and repro.*): jax locks the device count at first init.  The dry run
# lowers for 512 placeholder CPU devices by design, so it is pinned to the
# CPU and never reaches for an accelerator its parent may hold.  These
# flags are set ONLY here — tests and benchmarks see the real single CPU
# device.

import argparse        # noqa: E402
import dataclasses     # noqa: E402
import gc              # noqa: E402
import json            # noqa: E402
import time            # noqa: E402
import traceback       # noqa: E402
from pathlib import Path  # noqa: E402

import jax             # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import SHAPES, all_cells, get_config, get_shape  # noqa: E402
from repro.launch import hlo_analysis  # noqa: E402
from repro.launch.mesh import (  # noqa: E402
    HBM_BW, ICI_BW, PEAK_FLOPS_BF16, make_production_mesh,
)
from repro.launch.specs import lower_target, model_flops  # noqa: E402
from repro.train.train_step import TrainHParams  # noqa: E402


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             hp: TrainHParams = None, variant: str = "baseline",
             rules_override=None, save_hlo: bool = False,
             out_dir: Path = Path("artifacts/dryrun"), **ctx_opts) -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "devices": n_dev, "variant": variant, "ok": False,
    }
    t0 = time.time()
    try:
        fn, args, shards, donate = lower_target(cfg, shape, mesh, hp=hp,
                                                rules_override=rules_override,
                                                **ctx_opts)
        with mesh:
            lowered = jax.jit(fn, in_shardings=shards,
                              donate_argnums=donate).lower(*args)
            rec["lower_s"] = round(time.time() - t0, 2)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 2)

        ma = compiled.memory_analysis()
        rec["memory_per_device"] = {
            "arguments": ma.argument_size_in_bytes,
            "outputs": ma.output_size_in_bytes,
            "temps": ma.temp_size_in_bytes,
            "aliased": ma.alias_size_in_bytes,
            "total_live": ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes,
        }
        ca = compiled.cost_analysis() or {}
        rec["xla_cost_analysis"] = {
            k: v for k, v in ca.items()
            if k in ("flops", "bytes accessed") and v == v}

        txt = compiled.as_text()
        rec["hlo_chars"] = len(txt)
        st = hlo_analysis.analyze(txt)
        rec["per_device"] = {
            "flops": st.flops,
            "bytes_accessed": st.bytes_accessed,
            "bytes_hbm_est": st.bytes_hbm_est,
            "bytes_dot": st.bytes_dot,
            "bytes_entry": st.bytes_entry,
            "collective_bytes": st.collective_bytes,
            "collective_count": st.collective_count,
            "collective_bytes_total": st.total_collective_bytes,
            "dot_count": st.dot_count,
            "while_trips": st.while_trips[:50],
        }
        mf = model_flops(cfg, shape)
        rec["model_flops_global"] = mf
        rec["roofline"] = roofline_terms(st, n_dev, mf)
        rec["ok"] = True
        if save_hlo:
            hlo_path = out_dir / f"{arch}__{shape_name}__{rec['mesh']}__{variant}.hlo"
            hlo_path.write_text(txt)
            rec["hlo_file"] = str(hlo_path)
    except Exception as e:  # noqa: BLE001 — record the failure, don't crash the sweep
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    return rec


def roofline_terms(st: hlo_analysis.HloStats, n_dev: int, model_flops_global: float):
    """Three-term roofline (seconds) from per-device HLO stats."""
    t_compute = st.flops / PEAK_FLOPS_BF16
    t_memory = st.bytes_hbm_est / HBM_BW
    t_coll = st.total_collective_bytes / ICI_BW
    dominant = max(
        (("compute", t_compute), ("memory", t_memory), ("collective", t_coll)),
        key=lambda kv: kv[1])[0]
    hlo_flops_global = st.flops * n_dev
    return {
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "bound_s": max(t_compute, t_memory, t_coll),
        "model_flops/hlo_flops": (
            model_flops_global / hlo_flops_global if hlo_flops_global else 0.0),
        "mfu_upper_bound": (
            model_flops_global / (max(t_compute, t_memory, t_coll)
                                  * n_dev * PEAK_FLOPS_BF16)
            if max(t_compute, t_memory, t_coll) > 0 else 0.0),
    }


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--remat-segment", type=int, default=0)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--ce-chunk", type=int, default=1024)
    ap.add_argument("--moe-impl", choices=["dense", "ep"], default="dense")
    ap.add_argument("--no-gather-fsdp", action="store_true",
                    help="keep FSDP shard on weights (decode variant)")
    ap.add_argument("--opt-impl", choices=["adamw", "adamw8bit"],
                    default="adamw")
    ap.add_argument("--rules", default="default",
                    help="named sharding rules override (see NAMED_RULES)")
    ap.add_argument("--save-hlo", action="store_true")
    args = ap.parse_args()

    from repro.models.sharding import NAMED_RULES  # noqa: E402
    rules_override = NAMED_RULES[args.rules]

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    hp = TrainHParams(remat=args.remat or None, n_micro=args.n_micro,
                      ce_chunk=args.ce_chunk,
                      remat_segment=args.remat_segment,
                      opt_impl=args.opt_impl)

    cells = []
    if args.all:
        for arch, shapes in all_cells().items():
            cells += [(arch, s) for s in shapes]
    else:
        cells = [(args.arch, args.shape)]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    n_fail = 0
    for arch, shape_name in cells:
        for mp in meshes:
            mesh_tag = "2x16x16" if mp else "16x16"
            key = f"{arch}__{shape_name}__{mesh_tag}__{args.variant}"
            path = out_dir / (key + ".json")
            rec = run_cell(arch, shape_name, mp, hp=hp, variant=args.variant,
                           save_hlo=args.save_hlo, out_dir=out_dir,
                           rules_override=rules_override,
                           moe_impl=args.moe_impl,
                           gather_fsdp=not args.no_gather_fsdp)
            path.write_text(json.dumps(rec, indent=1, default=float))
            if rec["ok"]:
                r = rec["roofline"]
                print(f"OK   {key}  lower={rec['lower_s']}s compile={rec['compile_s']}s "
                      f"dom={r['dominant']} bound={r['bound_s']*1e3:.2f}ms "
                      f"mfu_ub={r['mfu_upper_bound']:.3f} "
                      f"mem={rec['memory_per_device']['total_live']/2**30:.2f}GiB",
                      flush=True)
            else:
                n_fail += 1
                print(f"FAIL {key}: {rec['error']}", flush=True)
            gc.collect()
    if n_fail:
        raise SystemExit(f"{n_fail} cell(s) failed")


if __name__ == "__main__":
    main()
