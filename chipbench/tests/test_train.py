"""CPU rehearsal of the training cell on four virtual devices, and its
check failing where the timed path is broken underneath.

    python -m pytest -q chipbench/tests

The cell runs at tiny widths through the harness with the look for a
chip skipped.  Each case runs in a child process: the four virtual
devices have to be asked for before JAX starts.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
TINY = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
        "vocab_size": 512, "global_batch": 8, "seq_len": 64, "ce_chunk": 32}

CHILD = r"""
import json, sys, time
sys.path[:0] = [{root!r}, {src!r}]
fault = {fault!r}
if fault == "state_unchanged":
    import repro.train.train_step as ts
    ts.adamw_update = lambda p, g, s, step, lr, cfg: (p, s, 0.0)
elif fault == "half_batch":
    import repro.models.model as M
    inner = M.loss_fn
    def loss_fn(cfg, params, batch, *a, **kw):
        half = {{k: v[: v.shape[0] // 2] for k, v in batch.items()}}
        return inner(cfg, params, half, *a, **kw)
    M.loss_fn = loss_fn
elif fault == "no_exchange":
    import repro.train.train_step as ts
    ts.barrier_sync_grads = lambda g, *a, **kw: g
from pathlib import Path
from chipbench.harness import run_cell
res = run_cell("sync.lazy_object", {seed}, 0.5, False,
               t_start=time.perf_counter(), require_chip=False,
               config_overrides={tiny!r}, root=Path({bench_root!r}))
print(json.dumps(res))
"""


# the cell's entries, added to a copy of BENCHMARK.json where it lacks
# them, so the cell can be rehearsed before it is listed
CELL = {
    "config": {"name": "internlm2-1.8b-d2-dp4", "source": "test",
               "file": "chipbench/configs/internlm2-1.8b-d2-dp4.json",
               "reduced": ["num_hidden_layers"], "why": "test"},
    "workload": {"name": "sync.lazy_object",
                 "config": "internlm2-1.8b-d2-dp4",
                 "traffic": "lazy_object", "chips": 4, "why": "test"},
    "end_to_end": {"name": "train_step_ms", "unit": "ms",
                   "better": "lower", "bound": 0.05,
                   "source": "host_clock",
                   "workloads": ["sync.lazy_object"]},
}


@pytest.fixture(scope="module")
def bench_root(tmp_path_factory):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    if any(w["name"] == "sync.lazy_object" for w in bench["workloads"]):
        return ROOT
    root = tmp_path_factory.mktemp("bench")
    shutil.copytree(ROOT / "chipbench", root / "chipbench")
    (root / "src").symlink_to(ROOT / "src")
    bench["configs"].append(CELL["config"])
    bench["workloads"].append(CELL["workload"])
    bench["end_to_end"].insert(0, CELL["end_to_end"])
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run_child(bench_root, fault=None, seed=2 ** 31 + 3):
    code = CHILD.format(root=str(ROOT), src=str(ROOT / "src"), fault=fault,
                        seed=seed, tiny=TINY, bench_root=str(bench_root))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_train_cell_runs_and_is_correct(bench_root):
    res = run_child(bench_root)
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == {"train_step_ms", "setup_s"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "no_exchange"])
def test_broken_step_is_not_correct(bench_root, fault):
    res = run_child(bench_root, fault)
    assert not res["correct"], res["checks"]
