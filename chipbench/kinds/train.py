"""Training cells: data-parallel steps of the RegC gradient sync.

The configuration gives the model (published widths, cut depth), the
global batch, the micro-batches, the optimizer and the sync policy.  The
benchmark makes the weights and the optimizer state on the device in one
jitted call from the seed, in the parameter layout the system takes, and
draws every step's batch from the seed and the step index.  Set-up
builds one compiled step (``make_train_step_regc`` on a ``("data",)``
mesh over the cell's chips) and drives it through its first three steps,
recording what the check compares; the window then runs further steps
of the same object, each ending in ``block_until_ready``.

The check runs ``chipbench/reference/internlm2.py`` over the same three
batches from the same weights, on one device, and compares each step's
loss, the norm of each leaf's first gradient as the optimizer got it,
and the norm of each leaf's change after the three steps.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from chipbench import flops
from chipbench.reference import internlm2 as ref

SPAN_NAMES = ("train.step",)
COLLECTIVES = (r"all-reduce", r"all-gather", r"reduce-scatter",
               r"collective-permute", r"all-to-all")
CHECKED_STEPS = 3


def leaf_shapes(m: Dict) -> Dict:
    """The parameter tree the system takes, as (shape, init scale); a
    scale of 0 is a norm gain, drawn as zeros."""
    L, d, V = m["num_hidden_layers"], m["hidden_size"], m["vocab_size"]
    Hq, Hkv, D = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    f = m["intermediate_size"]
    layer = {"ln": ((L, d), 0.0),
             "wq": ((L, d, Hq, D), d ** -0.5),
             "wk": ((L, d, Hkv, D), d ** -0.5),
             "wv": ((L, d, Hkv, D), d ** -0.5),
             "wo": ((L, Hq, D, d), (Hq * D) ** -0.5),
             "ln_mlp": ((L, d), 0.0),
             "mlp_w1": ((L, d, f), d ** -0.5),
             "mlp_w3": ((L, d, f), d ** -0.5),
             "mlp_w2": ((L, f, d), f ** -0.5)}
    return {"embed": ((V, d), 1.0), "final_ln": ((d,), 0.0),
            "blocks": [layer], "lm_head": ((d, V), d ** -0.5)}


def _key(seed: int):
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed // (1 << 31))


def make_state(m: Dict, seed: int):
    """Weights and zero AdamW moments from the seed, one jitted call."""
    import jax
    import jax.numpy as jnp
    shapes = leaf_shapes(m)
    is_spec = lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)  # noqa: E731
    leaves, tdef = jax.tree.flatten(shapes, is_leaf=is_spec)

    def init(key):
        keys = jax.random.split(key, len(leaves))
        arrs = [jnp.zeros(s, jnp.float32) if sc == 0.0 else
                sc * jax.random.normal(k, s, jnp.float32)
                for (s, sc), k in zip(leaves, keys)]
        p = jax.tree.unflatten(tdef, arrs)
        z = jax.tree.map(jnp.zeros_like, p)
        return p, {"m": z, "v": jax.tree.map(jnp.zeros_like, p)}
    return init, _key(seed)


def make_batch(m: Dict, c: Dict, seed: int, step: int):
    """Step ``step``'s global batch: tokens and next-token targets drawn
    uniformly from the vocabulary, different rows every step."""
    import jax
    k = jax.random.fold_in(jax.random.fold_in(_key(seed), 1 << 20), step)
    k1, k2 = jax.random.split(k)
    shape = (c["global_batch"], c["seq_len"])
    return {"tokens": jax.random.randint(k1, shape, 0, m["vocab_size"]),
            "targets": jax.random.randint(k2, shape, 0, m["vocab_size"])}


def model_of(c: Dict) -> Dict:
    keys = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "vocab_size", "rope_theta", "rms_norm_eps")
    return {k: c[k] for k in keys}


def _norms(tree):
    import jax
    import jax.numpy as jnp
    return [jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(tree)]


def leaf_norms(tree) -> List[float]:
    """Each leaf's L2 norm (one compiled program per tree shape)."""
    import jax
    return [float(x) for x in jax.jit(_norms)(tree)]


def gap_by_leaf(got: List[float], want: List[float], keep=None) -> float:
    """The widest gap between two lists of leaf norms, each against the
    reference's norm of that leaf or of the median leaf, the larger."""
    want_a = np.asarray(want, np.float64)
    got_a = np.asarray(got, np.float64)
    idx = np.arange(len(want_a)) if keep is None else np.asarray(keep)
    med = float(np.median(want_a[idx]))
    den = np.maximum(want_a[idx], med)
    return float(np.max(np.abs(got_a[idx] - want_a[idx]) / den))


def reference_run(c: Dict, seed: int, precision: str,
                  fault: Optional[str] = None, chips: int = 1) -> Dict:
    """The reference's three steps: losses, first-gradient leaf norms and
    leaf change norms.  ``fault`` plants a fault in it, for the limits'
    upper readings: ``half_batch`` takes the mean over half the rows,
    ``no_exchange`` over the rows of the first of ``chips`` chips only,
    as a step whose gradient is never exchanged."""
    import jax
    m, o = model_of(c), c["optimizer"]
    init, key = make_state(m, seed)
    dev = jax.devices()[0]
    with jax.default_device(dev), jax.default_matmul_precision(precision):
        p, st = jax.jit(init)(key)
        p0 = jax.device_get(p)
        mm, vv = st["m"], st["v"]
        losses, g1 = [], None
        okey = tuple(sorted(o.items()))
        for i in range(CHECKED_STEPS):
            b = jax.device_get(make_batch(m, c, seed, i))
            tok, tgt = b["tokens"], b["targets"]
            keep = {"half_batch": len(tok) // 2,
                    "no_exchange": len(tok) // chips}.get(fault, len(tok))
            tok, tgt = tok[:keep], tgt[:keep]
            loss, g = ref.loss_and_grad(p, tok, tgt, m, c["reference_rows"])
            p, mm, vv, gc = ref.adamw(p, g, mm, vv, float(i),
                                      ref.lr_at(i, o), okey)
            losses.append(float(loss))
            if i == 0:
                g1 = leaf_norms(gc)
                graw = leaf_norms(g)
        change = leaf_norms(jax.tree.map(lambda a, b: a - b, p, p0))
    return {"losses": losses, "grad_norms": g1, "raw_grad_norms": graw,
            "change_norms": change}


def compare(got: Dict, want: Dict, limits: Dict) -> Dict[str, dict]:
    """Per-step loss, first gradient and change after three steps, each
    by its widest gap; the change leaves out leaves whose reference
    gradient is under a thousandth of the median leaf's."""
    lw = np.asarray(want["losses"])
    lg = np.asarray(got["losses"])
    loss_gap = float(np.max(np.abs(lg - lw) / np.abs(lw)))
    g = np.asarray(want["raw_grad_norms"])
    keep = np.nonzero(g >= 1e-3 * np.median(g))[0]
    return {
        "loss_gap": {"value": loss_gap, "limit": limits["loss_gap"]},
        "grad_gap": {"value": gap_by_leaf(got["grad_norms"],
                                          want["grad_norms"]),
                     "limit": limits["grad_gap"]},
        "update_gap": {"value": gap_by_leaf(got["change_norms"],
                                            want["change_norms"], keep),
                       "limit": limits["update_gap"]},
    }


class Run:
    """One training cell's set-up, steps, layer context and check."""

    span_names = SPAN_NAMES

    def __init__(self, cell, seed: int, events, traced: bool):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.configs.base import LayerSpec, ModelConfig
        from repro.optim.adamw import AdamWConfig
        from repro.regc_sync.policies import RegCSyncPolicy
        from repro.train.train_step import TrainHParams, make_train_step_regc
        self.cell, self.seed, self.traced = cell, seed, traced
        c = self.c = cell.config
        m = self.m = model_of(c)
        jax.config.update("jax_default_matmul_precision",
                          c["matmul_precision"])
        mcfg = ModelConfig(
            name=cell.name, family="dense",
            n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
            n_heads=m["num_attention_heads"],
            n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
            d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
            pattern=(LayerSpec(kind="attn", mlp="dense"),),
            rope_theta=m["rope_theta"], norm_eps=m["rms_norm_eps"],
            tie_embeddings=False)
        o = c["optimizer"]
        hp = TrainHParams(
            lr=o["lr"], warmup=o["warmup"], total_steps=o["total_steps"],
            adamw=AdamWConfig(b1=o["b1"], b2=o["b2"], eps=o["eps"],
                              weight_decay=o["weight_decay"],
                              clip_norm=o["clip_norm"]),
            n_micro=c["n_micro"], remat=None, ce_chunk=c["ce_chunk"],
            sync=RegCSyncPolicy(c["sync"]["ordinary_sync"],
                                c["sync"]["granularity"]))
        devs = jax.devices()[:cell.chips]
        mesh = Mesh(np.asarray(devs), ("data",))
        self.rep = NamedSharding(mesh, P())
        self.bsh = NamedSharding(mesh, P("data"))
        self.step_fn = jax.jit(make_train_step_regc(mcfg, hp, mesh,
                                                    dp_axes=("data",)),
                               donate_argnums=(0, 1))
        init, key = make_state(m, seed)
        self.params, self.opt = jax.jit(init, out_shardings=self.rep)(key)
        self._batch = jax.jit(lambda s: make_batch(m, c, seed, s))
        self._jnp = jnp
        self.iters = 0
        self.spans: Optional[List] = [] if traced else None
        self._record = None
        self.got = self._first_steps()

    def _one_step(self):
        import jax
        b = jax.device_put(self._batch(self.iters), self.bsh)
        step = jax.device_put(self._jnp.asarray(self.iters, self._jnp.int32),
                              self.rep)
        self.params, self.opt, met = self.step_fn(self.params, self.opt, b,
                                                  step)
        jax.block_until_ready(self.params)
        self.iters += 1
        return met

    def _first_steps(self) -> Dict:
        """The first steps through the window's own call, recorded."""
        import jax
        p0 = jax.device_get(self.params)
        losses = []
        for i in range(CHECKED_STEPS):
            met = self._one_step()
            losses.append(float(met["loss"]))
            if i == 0:
                b1 = self.c["optimizer"]["b1"]
                g1 = [x / (1.0 - b1) for x in leaf_norms(self.opt["m"])]
        p3 = jax.device_get(self.params)
        change = [float(np.sqrt(np.sum(np.square(
            a.astype(np.float64) - b.astype(np.float64)))))
            for a, b in zip(jax.tree.leaves(p3), jax.tree.leaves(p0))]
        return {"losses": losses, "grad_norms": g1, "change_norms": change}

    def iteration(self):
        if self.spans is None:
            self._one_step()
            return
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("train.step"):
            self._one_step()
        self.spans.append(("train.step", time.perf_counter() - t0))

    def program_compiles(self) -> int:
        return 0

    def start_window(self):
        if self.spans is not None:
            self.spans.clear()

    def end_window(self):
        pass

    def check_device_path(self):
        pass

    def end_to_end(self, window_s: float, n: int) -> dict:
        return {"train_step_ms": window_s / n * 1e3}

    def layer_context(self, tr, peaks, window_s: float, n: int) -> dict:
        return {"iters": n, "window_s": window_s, "spans": self.spans,
                "counters": {}, "trace": tr, "peaks": peaks,
                "chips": self.cell.chips, "collectives": COLLECTIVES,
                "step_flops": flops.train_step_flops(
                    self.m, self.c["global_batch"], self.c["seq_len"])}

    def verify(self) -> Dict[str, dict]:
        self.params = self.opt = self.step_fn = None     # free the program
        want = reference_run(self.c, self.seed, self.c["matmul_precision"])
        return compare(self.got, want, self.c["limits"])


LOWER_PRECISION = {"highest": "high", "high": "bfloat16",
                   "default": "bfloat16"}


def control_readings(cell, seed: int, result: dict) -> dict:
    """Upper readings: the control (the reference at the precision below
    the configuration's) and the faults planted in the reference put in
    the program's place, each against the reference."""
    c = cell.config
    want = reference_run(c, seed, c["matmul_precision"])
    out = {"control": compare(
        reference_run(c, seed, LOWER_PRECISION[c["matmul_precision"]]),
        want, c["limits"])}
    for fault in ("half_batch", "no_exchange"):
        got = reference_run(c, seed, c["matmul_precision"], fault=fault,
                            chips=cell.chips)
        out[fault] = compare(got, want, c["limits"])
    return out
