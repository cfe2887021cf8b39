"""Core layer primitives (pure functions over param pytrees).

Every ``init_*`` returns ``(params, logical_axes)`` with identical tree
structure; logical axis names are mapped to mesh axes by
``repro.sharding.specs``.  Compute is the pure-jnp path on every backend;
XLA fuses it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .costing import xmap

NEG_INF = -1e30


# ---------------------------------------------------------------- inits ----
def _dense_init(key, shape, axes, scale=None, dtype=jnp.bfloat16):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape, dtype) * scale, axes)


def _zeros_init(shape, axes, dtype=jnp.bfloat16):
    return (jnp.zeros(shape, dtype), axes)


def _ones_init(shape, axes, dtype=jnp.bfloat16):
    return (jnp.ones(shape, dtype), axes)


# ----------------------------------------------------------------- norm ----
def rms_norm(x, w, eps=1e-5):
    # square in input dtype, accumulate in f32: avoids a full-tensor f32
    # convert of the residual stream (XLA hoists that out of the layer loop,
    # materializing the whole remat stack in f32 — 2x activation memory)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True, dtype=jnp.float32)
    wb = w.reshape((1,) * (x.ndim - w.ndim) + w.shape)
    return (x * lax.rsqrt(var + eps).astype(x.dtype)) * wb


# ----------------------------------------------------------------- rope ----
def rope(x, positions, theta=1e6):
    """x: [..., S, H, hd]; positions: [..., S] int32."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    # broadcast positions [..., S] against freqs -> [..., S, 1, half]
    pos = positions[..., :, None, None].astype(jnp.float32)
    ang = pos * freqs.reshape((1,) * (pos.ndim - 1) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    cos = cos.astype(x.dtype).reshape((1,) * (x.ndim - cos.ndim) + cos.shape)
    sin = sin.astype(x.dtype).reshape((1,) * (x.ndim - sin.ndim) + sin.shape)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def sinusoidal_pos(S, d, dtype=jnp.bfloat16):
    pos = jnp.arange(S, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32) * (-9.21034 / d))
    pe = jnp.zeros((S, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div[None, :]))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div[None, : (d - d // 2)]))
    return pe.astype(dtype)


# ------------------------------------------------------------- attention ---
def init_attention(key, cfg, d_model=None):
    d = d_model or cfg.d_model
    hd, H, KV = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    p, a = {}, {}
    p["wq"], a["wq"] = _dense_init(ks[0], (d, H * hd), ("embed", "heads"))
    p["wk"], a["wk"] = _dense_init(ks[1], (d, KV * hd), ("embed", "kv"))
    p["wv"], a["wv"] = _dense_init(ks[2], (d, KV * hd), ("embed", "kv"))
    p["wo"], a["wo"] = _dense_init(ks[3], (H * hd, d), ("heads", "embed"))
    return p, a


def attention(p, x, cfg, positions, causal=True, window=None,
              cache=None, cache_index=None, cross_kv=None):
    """x: [B, S, d].  Returns (out [B, S, d], new_cache | None).

    cache: dict(k=[B, KV, Smax, hd], v=...) for decode; cache_index: current
    length (tokens already in cache).  cross_kv: precomputed (k, v) for
    cross-attention (ignores cache/causal)."""
    B, S, d = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    if cross_kv is None:
        k = (x @ p["wk"]).reshape(B, S, KV, hd)
        v = (x @ p["wv"]).reshape(B, S, KV, hd)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    else:
        k, v = cross_kv  # [B, Skv, KV, hd]

    new_cache = None
    if cache is not None and cross_kv is None:
        # decode: RING-buffer cache of size eff (== window for SWA models —
        # decode never reads past the window, so long_500k SWA decode keeps
        # an O(window) cache).  slot(pos) = pos % eff.
        eff = cache["k"].shape[2]
        slot = cache_index % eff
        kc = lax.dynamic_update_slice(
            cache["k"], k.transpose(0, 2, 1, 3).astype(cache["k"].dtype),
            (0, 0, slot, 0))
        vc = lax.dynamic_update_slice(
            cache["v"], v.transpose(0, 2, 1, 3).astype(cache["v"].dtype),
            (0, 0, slot, 0))
        new_cache = {"k": kc, "v": vc}
        kk = kc.transpose(0, 2, 1, 3)  # [B, eff, KV, hd]
        vv = vc.transpose(0, 2, 1, 3)
        j = jnp.arange(eff)
        # true position held by slot j (largest p <= cache_index, p≡j mod eff)
        kv_positions = j + ((cache_index - j) // eff) * eff
    else:
        kk, vv = k, v
        kv_positions = jnp.arange(kk.shape[1])

    G = H // KV
    qg = q.reshape(B, S, KV, G, hd)
    masked = cross_kv is None
    out = _sdpa_chunked(qg, kk, vv, positions, kv_positions,
                        causal=causal and masked, window=window if masked
                        else None, masked=masked)
    out = out.reshape(B, S, H * hd)
    return out @ p["wo"], new_cache


def _sdpa_chunked(qg, kk, vv, positions, kv_positions, causal, window,
                  masked=True, chunk=512):
    """Query-chunked attention: never materializes the full [S, T] score
    matrix (jnp flash; the Pallas kernel replaces this on real TPU).

    qg: [B, S, KV, G, hd]; kk/vv: [B, T, KV, hd]."""
    B, S, KV, G, hd = qg.shape
    scale = hd ** -0.5
    kf = kk.astype(jnp.float32)
    vf = vv.astype(jnp.float32)
    tpos = kv_positions

    def block(args):
        qc, spos_c = args                      # [B, c, KV, G, hd], [c]
        s = jnp.einsum("bskgd,btkd->bkgst", qc.astype(jnp.float32),
                       kf) * scale
        if masked:
            m = tpos[None, :] >= 0
            if causal:
                m &= tpos[None, :] <= spos_c[:, None]
            if window is not None:
                m &= tpos[None, :] > spos_c[:, None] - window
            s = jnp.where(m[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bkgst,btkd->bskgd", p, vf)

    if S <= chunk:
        return block((qg, positions)).astype(qg.dtype)
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    n = S // chunk
    qs = qg.reshape(B, n, chunk, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
    ps = positions.reshape(n, chunk)
    out = xmap(block, (qs, ps))
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(
        B, S, KV, G, hd).astype(qg.dtype)


# ----------------------------------------------------------------- mlp -----
def init_mlp(key, cfg, d_model=None, d_ff=None):
    d = d_model or cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p, a = {}, {}
    p["w1"], a["w1"] = _dense_init(ks[0], (d, f), ("embed", "ff"))
    p["w3"], a["w3"] = _dense_init(ks[1], (d, f), ("embed", "ff"))
    p["w2"], a["w2"] = _dense_init(ks[2], (f, d), ("ff", "embed"))
    return p, a


def mlp(p, x):
    return (jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])) @ p["w2"]


# ------------------------------------------------------------- lm head -----
def chunked_xent(h, w_unembed, targets, valid=None, chunk=512):
    """Cross-entropy without materializing [B, S, V]: scan over seq chunks.

    h: [B, S, d]; w_unembed: [d, V]; targets: [B, S] int32.
    Returns mean nll over valid positions."""
    B, S, d = h.shape
    n = max(1, S // chunk)
    while S % n:
        n -= 1
    c = S // n
    hc = h.reshape(B, n, c, d).transpose(1, 0, 2, 3)
    tc = targets.reshape(B, n, c).transpose(1, 0, 2)
    vc = (valid.reshape(B, n, c).transpose(1, 0, 2)
          if valid is not None else jnp.ones_like(tc, bool))

    @jax.checkpoint  # never save per-chunk logits for backward: recompute
    def chunk_loss(args):
        hh, tt, vv = args
        logits = (hh @ w_unembed).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tt[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * vv
        return nll.sum(), vv.sum()

    losses, counts = xmap(chunk_loss, (hc, tc, vc))
    return losses.sum() / jnp.maximum(counts.sum(), 1)
