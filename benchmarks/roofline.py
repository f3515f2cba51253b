"""Roofline summary benchmark: condense the dry-run artifacts into the
per-cell three-term table (compute / memory / collective seconds, dominant
term, MFU upper bound).  The full dry-run sweep is launched via
``python -m repro.launch.dryrun --all`` (512 placeholder devices); this
reader never initializes extra devices — in a fresh checkout it
auto-generates a small seed set of cells in a subprocess on first run
(``--no-auto`` disables)."""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from pathlib import Path

from benchmarks.common import print_rows, write_csv

ART = Path("artifacts/dryrun")

# small cells lowered on first run when no artifacts exist yet (an attn
# and an SSM arch; ~10-20s each — the 512-device sweep stays manual)
SEED_CELLS = (("internlm2-1.8b", "train_4k"), ("mamba2-2.7b", "train_4k"))


def ensure_artifacts(variant: str = "baseline") -> bool:
    """Generate the seed dry-run cells if none exist for ``variant``.
    Runs dryrun in a subprocess: it forces a 512-device jax at import,
    which must not leak into this process.  Returns True when artifacts
    are available afterwards."""
    if any(ART.glob(f"*__{variant}.json")):
        return True
    if variant != "baseline":
        return False               # only the baseline seed set is automatic
    print(f"no dry-run artifacts under {ART}; generating seed cells "
          f"{SEED_CELLS} (use `python -m repro.launch.dryrun --all` for "
          "the full sweep)", flush=True)
    # the dry run lowers for placeholder CPU devices by design: the child
    # never reaches for an accelerator this process may hold
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for arch, shape in SEED_CELLS:
        try:
            r = subprocess.run(
                [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
                 "--shape", shape, "--mesh", "single", "--out", str(ART)],
                capture_output=True, text=True, timeout=560, env=env)
        except (subprocess.TimeoutExpired, OSError) as e:
            print(f"dry-run seed cell {arch}/{shape} failed: {e}", flush=True)
            continue
        if r.returncode != 0:
            print(f"dry-run seed cell {arch}/{shape} failed:\n"
                  f"{r.stdout[-1000:]}\n{r.stderr[-1000:]}", flush=True)
    return any(ART.glob(f"*__{variant}.json"))


def load_rows(variant: str = "baseline", mesh: str = None):
    rows = []
    for f in sorted(ART.glob(f"*__{variant}.json")):
        r = json.loads(f.read_text())
        if not r.get("ok"):
            continue
        if mesh and r["mesh"] != mesh:
            continue
        roof = r["roofline"]
        rows.append({
            "arch": r["arch"], "shape": r["shape"], "mesh": r["mesh"],
            "variant": r["variant"],
            "t_compute_ms": round(roof["t_compute_s"] * 1e3, 3),
            "t_memory_ms": round(roof["t_memory_s"] * 1e3, 3),
            "t_collective_ms": round(roof["t_collective_s"] * 1e3, 3),
            "dominant": roof["dominant"],
            "model/hlo_flops": round(roof["model_flops/hlo_flops"], 3),
            "mfu_upper_bound": round(roof["mfu_upper_bound"], 4),
        })
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--no-auto", action="store_true",
                    help="do not auto-generate seed dry-run artifacts")
    args = ap.parse_args(argv)
    if not args.no_auto:
        ensure_artifacts(args.variant)
    rows = load_rows(args.variant, args.mesh)
    if not rows:
        print(f"no dry-run artifacts for variant={args.variant} "
              f"(run: python -m repro.launch.dryrun --all --mesh both)")
        return []
    write_csv(f"roofline_{args.variant}", rows)
    print_rows(rows)
    return rows


if __name__ == "__main__":
    main()
