"""Mamba-2 SSD (state-space duality) mixer — arXiv:2405.21060.

Train/prefill uses the chunked SSD algorithm: quadratic *within* a chunk
(maps onto the MXU), recurrent *across* chunks (``lax.scan`` carrying the
(B, H, P, N) state).  Decode is the O(1)-per-token recurrence.  The
intra-chunk part has a Pallas kernel (``repro.kernels.ssd_chunk``); this file
is the pure-XLA implementation used for CPU tests and dry-run lowering.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.sharding import ShardingCtx, constrain


def ssd_chunked(x, dt, A, B_, C_, *, chunk: int, initial_state=None):
    """Chunked SSD scan.

    x: (B, S, H, P)   dt: (B, S, H)  (already softplus'd, >0)
    A: (H,)           (negative)
    B_, C_: (B, S, G, N), H % G == 0
    Returns (y (B,S,H,P), final_state (B,H,P,N)).
    """
    Bb, S, H, P = x.shape
    G, N = B_.shape[2], B_.shape[3]
    Q = min(chunk, S)
    S_orig = S
    if S % Q:
        # pad with dt=0 tokens: zero input weight, unit decay -> state-neutral
        pad = Q - S % Q
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C_ = jnp.pad(C_, ((0, 0), (0, pad), (0, 0), (0, 0)))
        S = S + pad
    nc = S // Q
    hg = H // G  # heads per B/C group

    xc = x.reshape(Bb, nc, Q, H, P)
    dtc = dt.reshape(Bb, nc, Q, H).astype(jnp.float32)
    Bc = B_.reshape(Bb, nc, Q, G, N)
    Cc = C_.reshape(Bb, nc, Q, G, N)

    dA = dtc * A.astype(jnp.float32)                     # (B,nc,Q,H) negative
    cum = jnp.cumsum(dA, axis=2)                         # within-chunk cumsum

    # ---- intra-chunk (quadratic, MXU-friendly) ----------------------------
    CB = jnp.einsum("bcqgn,bckgn->bcgqk", Cc, Bc,
                    preferred_element_type=jnp.float32)  # (B,nc,G,Q,Q)
    CB = jnp.repeat(CB, hg, axis=2)                      # (B,nc,H,Q,Q)
    # decay_ij = exp(cum_i - cum_j), causal.  Mask BEFORE the exp: in the
    # non-causal triangle cum_i - cum_j > 0 and exp overflows to inf, which
    # the where() would hide in the forward but turn into 0*inf = NaN in the
    # backward (where-grad still differentiates the dead branch).
    cum_h = cum.transpose(0, 1, 3, 2)                    # (B,nc,H,Q)
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    delta = cum_h[..., :, None] - cum_h[..., None, :]
    decay = jnp.exp(jnp.where(causal, delta, -jnp.inf))  # exact 0 off-causal
    scores = CB * decay
    scores = scores * dtc.transpose(0, 1, 3, 2)[..., None, :]  # * dt_j
    y_intra = jnp.einsum("bchqk,bckhp->bcqhp", scores, xc,
                         preferred_element_type=jnp.float32)

    # ---- per-chunk input states ------------------------------------------
    last = cum_h[..., -1:]                               # (B,nc,H,1)
    w_in = jnp.exp(last - cum_h) * dtc.transpose(0, 1, 3, 2)  # (B,nc,H,Q)
    B_heads = jnp.repeat(Bc, hg, axis=3)                 # (B,nc,Q,H,N)
    chunk_states = jnp.einsum("bchq,bcqhn,bcqhp->bchpn",
                              w_in, B_heads, xc,
                              preferred_element_type=jnp.float32)

    # ---- inter-chunk recurrence ------------------------------------------
    chunk_decay = jnp.exp(jnp.sum(dA, axis=2))           # (B,nc,H)
    if initial_state is None:
        initial_state = jnp.zeros((Bb, H, P, N), jnp.float32)
    else:
        initial_state = initial_state.astype(jnp.float32)

    def step(h, inp):
        dec, s_in = inp                                  # (B,H), (B,H,P,N)
        h_prev = h
        h_new = h * dec[..., None, None] + s_in
        return h_new, h_prev

    dec_s = chunk_decay.transpose(1, 0, 2)               # (nc,B,H)
    st_s = chunk_states.transpose(1, 0, 2, 3, 4)         # (nc,B,H,P,N)
    final_state, prev_states = lax.scan(step, initial_state, (dec_s, st_s))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)   # (B,nc,H,P,N)

    # ---- inter-chunk output contribution ----------------------------------
    C_heads = jnp.repeat(Cc, hg, axis=3)                 # (B,nc,Q,H,N)
    y_inter = jnp.einsum("bcqhn,bchpn->bcqhp", C_heads, prev_states,
                         preferred_element_type=jnp.float32)
    y_inter = y_inter * jnp.exp(cum)[..., None]

    y = (y_intra + y_inter).reshape(Bb, S, H, P)
    return y[:, :S_orig], final_state


def ssd_reference(x, dt, A, B_, C_, *, initial_state=None):
    """O(S) sequential oracle (tests only)."""
    Bb, S, H, P = x.shape
    G, N = B_.shape[2], B_.shape[3]
    hg = H // G
    h0 = (jnp.zeros((Bb, H, P, N), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))
    dtf = dt.astype(jnp.float32)
    Bf = jnp.repeat(B_, hg, axis=2).astype(jnp.float32)  # (B,S,H,N)
    Cf = jnp.repeat(C_, hg, axis=2).astype(jnp.float32)

    def step(h, inp):
        xt, dtt, bt, ct = inp                            # (B,H,P),(B,H),(B,H,N)x2
        dec = jnp.exp(dtt * A.astype(jnp.float32))       # (B,H)
        h = h * dec[..., None, None] + jnp.einsum(
            "bh,bhn,bhp->bhpn", dtt, bt, xt)
        y = jnp.einsum("bhn,bhpn->bhp", ct, h)
        return h, y

    xs = (x.transpose(1, 0, 2, 3).astype(jnp.float32),
          dtf.transpose(1, 0, 2), Bf.transpose(1, 0, 2, 3),
          Cf.transpose(1, 0, 2, 3))
    hT, ys = lax.scan(step, h0, xs)
    return ys.transpose(1, 0, 2, 3), hT


# ---------------------------------------------------------------------------
# Full Mamba-2 block
# ---------------------------------------------------------------------------


def _causal_conv(seq, w, b, tail=None):
    """Depthwise causal conv1d.  seq: (B, S, Cdim); w: (K, Cdim); b: (Cdim,).
    tail: (B, K-1, Cdim) carried context (decode / prefill continuation)."""
    K = w.shape[0]
    Bb = seq.shape[0]
    if tail is None:
        tail = jnp.zeros((Bb, K - 1, seq.shape[-1]), seq.dtype)
    full = jnp.concatenate([tail, seq], axis=1)
    out = sum(
        full[:, i : i + seq.shape[1]] * w[i][None, None, :] for i in range(K)
    )
    new_tail = full[:, -(K - 1):] if K > 1 else tail
    return jax.nn.silu(out + b[None, None, :]), new_tail


def mamba2_block(params, x, cfg, ctx: Optional[ShardingCtx], *,
                 cache=None, mode: str = "train"):
    """mode: 'train' | 'prefill' | 'decode'.
    cache (decode): (conv_tail (B,K-1,conv_dim), ssm_state (B,H,P,N)).
    Returns (out, new_cache) — new_cache is None for train."""
    s = cfg.ssm
    B, S, d = x.shape
    d_in = s.expand * cfg.d_model
    gn = s.n_groups * s.d_state
    H = d_in // s.head_dim
    P = s.head_dim
    N = s.d_state
    G = s.n_groups
    # separate projections (clean TP sharding; no post-matmul slicing)
    z = jnp.einsum("bsd,de->bse", x, params["in_z"])
    xr = jnp.einsum("bsd,de->bse", x, params["in_x"])
    Br = jnp.einsum("bsd,de->bse", x, params["in_B"])
    Cr = jnp.einsum("bsd,de->bse", x, params["in_C"])
    dtr = jnp.einsum("bsd,dh->bsh", x, params["in_dt"])

    conv_in = jnp.concatenate([xr, Br, Cr], axis=-1)
    tail_in = cache[0] if (cache is not None) else None
    conv_out, new_tail = _causal_conv(conv_in, params["conv_w"], params["conv_b"],
                                      tail=tail_in)
    xr, Br, Cr = jnp.split(conv_out, [d_in, d_in + gn], axis=-1)

    xh = xr.reshape(B, S, H, P)
    xh = constrain(xh, ("batch", "seq", "ssm_in", None), ctx)
    Bm = Br.reshape(B, S, G, N)
    Cm = Cr.reshape(B, S, G, N)
    dt = jax.nn.softplus(dtr.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"].astype(jnp.float32))

    init_state = cache[1] if (cache is not None) else None
    if mode == "decode" and S == 1:
        # O(1) recurrence
        h = init_state.astype(jnp.float32)
        hg = H // G
        Bh = jnp.repeat(Bm, hg, axis=2)[:, 0]            # (B,H,N)
        Ch = jnp.repeat(Cm, hg, axis=2)[:, 0]
        dt0 = dt[:, 0]                                   # (B,H)
        dec = jnp.exp(dt0 * A)
        h = h * dec[..., None, None] + jnp.einsum(
            "bh,bhn,bhp->bhpn", dt0, Bh, xh[:, 0].astype(jnp.float32))
        y = jnp.einsum("bhn,bhpn->bhp", Ch, h)[:, None]  # (B,1,H,P)
        new_state = h
    else:
        y, new_state = ssd_chunked(xh, dt, A, Bm, Cm, chunk=s.chunk,
                                   initial_state=init_state)

    y = y + xh.astype(jnp.float32) * params["D"].astype(jnp.float32)[:, None]
    y = y.reshape(B, S, d_in)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    # gated RMSNorm
    var = jnp.mean(y * y, axis=-1, keepdims=True)
    y = y * lax.rsqrt(var + cfg.norm_eps) * (1.0 + params["gate_ln"].astype(jnp.float32))
    y = y.astype(x.dtype)
    out = jnp.einsum("bse,ed->bsd", y, params["out_proj"])

    new_cache = None if mode == "train" else (new_tail, new_state)
    return out, new_cache
