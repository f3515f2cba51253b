"""RegC at the training layer (DESIGN.md §2.2): measure what the paper's
dichotomy buys in a distributed trainer.

Compares gradient-sync policies on an 8-way DP mesh (subprocess with 8 host
devices; the bench process itself keeps 1 device):

* lazy/object    — RegC: local accumulation, one fine-grained psum per
                   parameter at the step barrier
* lazy/bucket    — RegC with page-like bucketing
* eager/object   — RC baseline: sync at every microbatch 'release'
* lazy/int8_ring — beyond-paper compressed ring (the diff analogue)

Metric: per-device collective bytes + message count from the lowered HLO
(exact), plus measured CPU wall-time per step (indicative only).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from benchmarks.common import print_rows, write_csv

SCRIPT = r"""
import json, time
import jax, jax.numpy as jnp
from repro.configs import get_reduced
from repro.launch import hlo_analysis
from repro.models import model as M
from repro.optim.adamw import init_opt_state
from repro.regc_sync.policies import RegCSyncPolicy
from repro.train.train_step import TrainHParams, make_train_step_regc

cfg = get_reduced("internlm2-1.8b", n_periods=2)
from repro.compat import make_mesh
mesh = make_mesh((8,), ("data",))
params = M.init_model_params(cfg, jax.random.PRNGKey(0), jnp.float32)
opt = init_opt_state(params)
ks = jax.random.split(jax.random.PRNGKey(1), 2)
B, S = 16, 64
batch = {"tokens": jax.random.randint(ks[0], (B, S), 0, cfg.vocab_size),
         "targets": jax.random.randint(ks[1], (B, S), 0, cfg.vocab_size)}
step0 = jnp.zeros((), jnp.int32)

POLICIES = [
    ("lazy_object",  RegCSyncPolicy("lazy", "object"), 2),
    ("lazy_bucket",  RegCSyncPolicy("lazy", "bucket", 1 << 20), 2),
    ("eager_object", RegCSyncPolicy("eager", "object"), 2),
    ("int8_ring",    RegCSyncPolicy("lazy", "object", compression="int8_ring"), 2),
]
rows = []
for tag, pol, n_micro in POLICIES:
    hp = TrainHParams(remat=None, ce_chunk=32, n_micro=n_micro, sync=pol)
    fn = make_train_step_regc(cfg, hp, mesh, dp_axes=("data",))
    jfn = jax.jit(fn)
    lowered = jfn.lower(params, opt, batch, step0)
    st = hlo_analysis.analyze(lowered.compile().as_text())
    t0 = time.perf_counter()
    out = jfn(params, opt, batch, step0)
    jax.block_until_ready(out[2]["loss"])
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(3):
        out = jfn(params, opt, batch, step0)
    jax.block_until_ready(out[2]["loss"])
    t_step = (time.perf_counter() - t0) / 3
    rows.append({
        "policy": tag,
        "collective_bytes_per_dev": st.total_collective_bytes,
        "coll_msgs": sum(st.collective_count.values()),
        "ar_bytes": st.collective_bytes.get("all-reduce", 0.0),
        "permute_bytes": st.collective_bytes.get("collective-permute", 0.0),
        "loss": float(out[2]["loss"]),
        "wall_s_per_step": round(t_step, 4),
    })
print("JSON" + json.dumps(rows))
"""


def main(argv=None):
    env = dict(os.environ)
    # the 8-way DP mesh is 8 virtual CPU devices by design: the child
    # never reaches for an accelerator the parent process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    line = [l for l in out.stdout.splitlines() if l.startswith("JSON")][0]
    rows = json.loads(line[4:])
    for r in rows:
        r["figure"] = "regc_training"
    rows = [{"figure": r.pop("figure"), **r} for r in rows]
    write_csv("regc_training", rows)
    print_rows(rows)
    return rows


if __name__ == "__main__":
    main()
