"""Plain reference of the training cell: internlm2's decoder, its loss, its
gradient and one AdamW step, in straightforward ``jax.numpy``.

It follows arXiv:2403.17297 and the model's published config: pre-norm
RMSNorm, rotary embeddings on the first and second halves of each head,
grouped-query attention with causal softmax at scale head_dim^-0.5, a
SwiGLU MLP, a final RMSNorm and an untied output head; the loss is the
mean token cross-entropy.  Two conventions follow the system's parameter
layout rather than the published checkpoint, since the weights are drawn
from the seed and never loaded: a norm's gain is stored as ``w`` and
applied as ``1 + w``, and per-layer weights are stacked on a leading
layer axis (``blocks[0][name][layer]``).  AdamW decays every stored leaf
of rank 2 or more, clips the gradient to global norm ``clip_norm``, and
follows a linear warm-up.

Nothing here imports the system under test.  It runs on one device, a
few sequences at a time, with matmuls at the precision its caller sets.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp


def rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def rope(x, theta):
    """x: (B, S, H, D); rotary position embedding over halves of D."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv      # (S, D/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(p, x, m: Dict):
    """One decoder layer; ``p`` holds this layer's weights."""
    eps, D = m["rms_norm_eps"], m["head_dim"]
    G = m["num_attention_heads"] // m["num_key_value_heads"]
    h = rmsnorm(x, p["ln"], eps)
    q = rope(jnp.einsum("bsd,dhk->bshk", h, p["wq"]), m["rope_theta"])
    k = rope(jnp.einsum("bsd,dhk->bshk", h, p["wk"]), m["rope_theta"])
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (D ** -0.5)
    S = x.shape[1]
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    x = x + jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    h = rmsnorm(x, p["ln_mlp"], eps)
    a = jax.nn.silu(h @ p["mlp_w1"]) * (h @ p["mlp_w3"])
    return x + a @ p["mlp_w2"]


def token_loss_sum(params, tokens, targets, m: Dict):
    """Summed cross-entropy over the tokens of a few sequences."""
    x = params["embed"][tokens]
    blocks = params["blocks"][0]
    for i in range(m["num_hidden_layers"]):
        x = layer({k: v[i] for k, v in blocks.items()}, x, m)
    x = rmsnorm(x, params["final_ln"], m["rms_norm_eps"])
    logits = x @ params["lm_head"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - ll)


@functools.partial(jax.jit, static_argnames=("mkey",))
def _grad_block(params, tokens, targets, mkey):
    return jax.value_and_grad(token_loss_sum)(params, tokens, targets,
                                              dict(mkey))


def loss_and_grad(params, tokens, targets, m: Dict, rows: int):
    """Mean token loss and its gradient over the batch, ``rows``
    sequences at a time."""
    mkey = tuple(sorted(m.items()))
    total, grads = 0.0, None
    for r in range(0, tokens.shape[0], rows):
        l, g = _grad_block(params, tokens[r:r + rows], targets[r:r + rows],
                           mkey)
        total = total + l
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    n = tokens.size
    return total / n, jax.tree.map(lambda a: a / n, grads)


def lr_at(step: int, o: Dict) -> float:
    """Linear warm-up to ``lr`` over ``warmup`` steps (the cell's steps all
    lie inside it)."""
    assert step < o["warmup"]
    return o["lr"] * (step + 1.0) / o["warmup"]


@functools.partial(jax.jit, static_argnames=("okey",))
def adamw(params, grads, m, v, step, lr, okey):
    """One AdamW step: clip to global norm, moments, bias correction,
    decoupled decay on leaves of rank >= 2."""
    o = dict(okey)
    sq = sum(jnp.sum(g * g) for g in jax.tree.leaves(grads))
    norm = jnp.sqrt(sq)
    scale = jnp.minimum(1.0, o["clip_norm"] / jnp.maximum(norm, 1e-12))
    grads = jax.tree.map(lambda g: g * scale, grads)
    t = step + 1.0
    bc1, bc2 = 1.0 - o["b1"] ** t, 1.0 - o["b2"] ** t

    def upd(p, g, mm, vv):
        mm = o["b1"] * mm + (1.0 - o["b1"]) * g
        vv = o["b2"] * vv + (1.0 - o["b2"]) * g * g
        d = (mm / bc1) / (jnp.sqrt(vv / bc2) + o["eps"])
        if p.ndim >= 2:
            d = d + o["weight_decay"] * p
        return p - lr * d, mm, vv

    out = jax.tree.map(upd, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda t3: t3[i], out,       # noqa: E731
                                  is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), pick(1), pick(2), grads
