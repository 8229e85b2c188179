"""Core transformer layers: norms, RoPE (standard/partial/M-RoPE), GQA
attention (causal / sliding-window / chunked / cross, with KV cache), MLPs.

All functions are pure; parameters arrive as dicts of arrays. ``*_specs``
builders produce the matching :class:`~repro.nn.param.ParamSpec` trees.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.nn.param import ParamSpec, fan_in_init, normal_init, ones_init, zeros_init
from repro.nn.sharding import logical_constraint


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_specs(cfg: ModelConfig, d: Optional[int] = None):
    d = d or cfg.d_model
    p = {"scale": ParamSpec((d,), jnp.float32, ones_init, ("norm",))}
    if cfg.norm == "layernorm":
        p["bias"] = ParamSpec((d,), jnp.float32, zeros_init, ("norm",))
    return p


def apply_norm(params, x, cfg: ModelConfig):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * params["scale"] + params["bias"]
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        y = x * jax.lax.rsqrt(ms + cfg.norm_eps) * params["scale"]
    return y.astype(dtype)


def rms_norm(x, scale, eps=1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return (y * scale).astype(dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def _rot_dims(cfg: ModelConfig) -> int:
    rot = int(cfg.hd * cfg.rotary_pct)
    return rot - rot % 2


def rope_angles(positions: jax.Array, cfg: ModelConfig) -> jax.Array:
    """positions: (..., S) or (3, B, S) for M-RoPE → angles (..., S, rot/2)."""
    rot = _rot_dims(cfg)
    half = rot // 2
    inv_freq = 1.0 / (
        cfg.rope_theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    )
    if cfg.rope_mode == "mrope":
        # positions: (3, B, S); mrope_sections sums to half.
        secs = cfg.mrope_sections
        assert sum(secs) == half, (secs, half)
        chan = jnp.concatenate(
            [jnp.full((s,), i, jnp.int32) for i, s in enumerate(secs)]
        )  # (half,) which position channel each freq uses
        pos = jnp.take(positions, chan, axis=0)  # (half, B, S)
        pos = jnp.moveaxis(pos, 0, -1)  # (B, S, half)
        return pos.astype(jnp.float32) * inv_freq
    return positions[..., None].astype(jnp.float32) * inv_freq


def apply_rope(x: jax.Array, angles: jax.Array, cfg: ModelConfig) -> jax.Array:
    """x: (B, S, H, D); angles: (B, S, half)."""
    rot = _rot_dims(cfg)
    if rot == 0 or cfg.rope_mode == "none":
        return x
    half = rot // 2
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., :half], xr[..., half:]
    cos = jnp.cos(angles)[..., None, :].astype(x.dtype)
    sin = jnp.sin(angles)[..., None, :].astype(x.dtype)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out, xp], axis=-1) if rot < x.shape[-1] else out


# ---------------------------------------------------------------------------
# Attention (GQA, causal / SWA / chunked / cross, cache-aware)
# ---------------------------------------------------------------------------

def attention_specs(cfg: ModelConfig, cross: bool = False):
    d, h, kvh, hd = cfg.d_model, cfg.num_heads, cfg.kv_heads, cfg.hd
    p = {
        "wq": ParamSpec((d, h, hd), cfg.pdtype, fan_in_init(0),
                        ("embed", "heads", None)),
        "wk": ParamSpec((d, kvh, hd), cfg.pdtype, fan_in_init(0),
                        ("embed", "kv_heads", None)),
        "wv": ParamSpec((d, kvh, hd), cfg.pdtype, fan_in_init(0),
                        ("embed", "kv_heads", None)),
        "wo": ParamSpec((h, hd, d), cfg.pdtype, fan_in_init(1),
                        ("heads", None, "embed")),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = ParamSpec((hd,), jnp.float32, ones_init, ("norm",))
        p["k_norm"] = ParamSpec((hd,), jnp.float32, ones_init, ("norm",))
    return p


def _attn_mask(q_pos, kv_pos, cfg: ModelConfig, causal: bool):
    """q_pos: (B, Sq), kv_pos: (B, Skv) → bool (B, Sq, Skv)."""
    qp = q_pos[:, :, None]
    kp = kv_pos[:, None, :]
    mask = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if causal:
        mask &= kp <= qp
    if cfg.sliding_window:
        mask &= (qp - kp) < cfg.sliding_window
    if cfg.attention_chunk:
        mask &= (qp // cfg.attention_chunk) == (kp // cfg.attention_chunk)
    return mask


def multihead_attention(
    params,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    angles: Optional[jax.Array] = None,
    kv_x: Optional[jax.Array] = None,
    kv_angles: Optional[jax.Array] = None,
    q_pos: Optional[jax.Array] = None,
    kv_pos: Optional[jax.Array] = None,
    causal: bool = True,
    cache=None,
    cache_index=None,
    kv_precomputed=None,
):
    """General attention.

    - self-attention: ``kv_x is None``
    - cross-attention: ``kv_x`` is the encoder memory (no rope, no causal)
    - decode: ``cache = dict(k=(B,S,KVH,D), v=...)`` and ``cache_index``
      scalar; new K/V written at ``cache_index``, attends over full cache.
      ``cache_index`` may also be a (B,) / (B, 1) vector of *per-row*
      write positions (continuous batching: every request sits at its own
      decode offset) — each row's K/V then lands at its own index, and the
      caller is responsible for passing per-row ``q_pos``/rope positions
      to match (``DecoderLM._with_cache`` derives both from the same
      index, so a vector index stays consistent end to end).

    Returns (out, new_cache).
    """
    B, Sq, _ = x.shape
    cross = kv_x is not None or kv_precomputed is not None
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    if kv_precomputed is not None:
        k, v = kv_precomputed
    else:
        src = kv_x if cross else x
        k = jnp.einsum("bsd,dhk->bshk", src, params["wk"].astype(x.dtype))
        v = jnp.einsum("bsd,dhk->bshk", src, params["wv"].astype(x.dtype))

    if cfg.qk_norm and not cross:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)

    if not cross and cfg.rope_mode != "none":
        if angles is not None:
            q = apply_rope(q, angles, cfg)
        ka = kv_angles if kv_angles is not None else angles
        if ka is not None:
            k = apply_rope(k, ka, cfg)

    new_cache = None
    if cache is not None:
        # write new kv at cache_index, then attend over the whole cache
        idx = cache_index
        if getattr(idx, "ndim", 0):
            # per-row write positions (continuous batching): row b's new
            # K/V lands at idx[b] of its own cache row
            row = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
                c, u, (i,) + (0,) * (c.ndim - 1)))
            idx_v = jnp.reshape(idx, (-1,))
            ck = row(cache["k"], k.astype(cache["k"].dtype), idx_v)
            cv = row(cache["v"], v.astype(cache["v"].dtype), idx_v)
        else:
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, idx, 0, 0)
            )
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, idx, 0, 0)
            )
        new_cache = {"k": ck, "v": cv}
        k, v = ck, cv
        Skv = k.shape[1]
        if kv_pos is None:
            kv_pos = jnp.broadcast_to(jnp.arange(Skv), (B, Skv))
    else:
        Skv = k.shape[1]

    if q_pos is None:
        q_pos = jnp.broadcast_to(jnp.arange(Sq), (B, Sq))
    if kv_pos is None:
        kv_pos = jnp.broadcast_to(jnp.arange(Skv), (B, Skv))

    k = logical_constraint(k, ("batch", "cache_seq", "cache_heads", None))
    v = logical_constraint(v, ("batch", "cache_seq", "cache_heads", None))

    is_causal = causal and not cross
    if (cfg.use_pallas and Sq == k.shape[1] and Sq % 128 == 0
            and cfg.hd in (64, 128) and cfg.rotary_pct == 1.0):
        # Pallas TPU flash kernel (interpret-mode off the TPU); full-seq paths
        from repro.kernels import interpret_mode
        from repro.kernels.flash_attention.ops import flash_attention_bshd

        out = flash_attention_bshd(
            q, k, v, causal=is_causal,
            window=cfg.sliding_window if is_causal else None,
            chunk=cfg.attention_chunk if is_causal else None,
            interpret=interpret_mode())
    elif Sq >= 1024 and Sq % 512 == 0 and k.shape[1] % 512 == 0:
        # Blockwise (flash-style) path: O(block²) live memory; mandatory at
        # the assigned shapes. Skips dead blocks for SWA/chunked masks.
        from repro.nn.flash import blockwise_attention

        out = blockwise_attention(
            q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=is_causal,
            window=cfg.sliding_window if is_causal else None,
            chunk=cfg.attention_chunk if is_causal else None,
        )
    else:
        out = gqa_attention(
            q, k, v, _attn_mask(q_pos, kv_pos, cfg, is_causal)
        )
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    out = logical_constraint(out, ("batch", "seq", "act_embed"))
    return out, new_cache


def gqa_attention(q, k, v, mask):
    """q: (B,Sq,H,D), k/v: (B,Skv,KVH,D), mask: (B,Sq,Skv) → (B,Sq,H,D)."""
    B, Sq, H, D = q.shape
    KVH = k.shape[2]
    G = H // KVH
    q = q.reshape(B, Sq, KVH, G, D)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(D)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return out.reshape(B, Sq, H, D)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_specs(cfg: ModelConfig, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.act in ("swiglu", "geglu"):
        return {
            "wi_gate": ParamSpec((d, f), cfg.pdtype, fan_in_init(0),
                                 ("embed", "mlp")),
            "wi_up": ParamSpec((d, f), cfg.pdtype, fan_in_init(0),
                               ("embed", "mlp")),
            "wo": ParamSpec((f, d), cfg.pdtype, fan_in_init(0),
                            ("mlp", "embed")),
        }
    return {
        "wi": ParamSpec((d, f), cfg.pdtype, fan_in_init(0), ("embed", "mlp")),
        "wo": ParamSpec((f, d), cfg.pdtype, fan_in_init(0), ("mlp", "embed")),
    }


def apply_mlp(params, x, cfg: ModelConfig):
    dt = x.dtype
    if cfg.act in ("swiglu", "geglu"):
        g = jnp.einsum("bsd,df->bsf", x, params["wi_gate"].astype(dt))
        u = jnp.einsum("bsd,df->bsf", x, params["wi_up"].astype(dt))
        g = jax.nn.silu(g) if cfg.act == "swiglu" else jax.nn.gelu(g)
        h = g * u
    else:
        h = jnp.einsum("bsd,df->bsf", x, params["wi"].astype(dt))
        h = jax.nn.gelu(h)
    h = logical_constraint(h, ("batch", "seq", "act_mlp"))
    return jnp.einsum("bsf,fd->bsd", h, params["wo"].astype(dt))


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embedding_specs(cfg: ModelConfig):
    p = {
        "embedding": ParamSpec(
            (cfg.vocab_size, cfg.d_model), cfg.pdtype, normal_init(0.02),
            ("vocab", "embed"),
        )
    }
    if not cfg.tie_embeddings:
        p["unembed"] = ParamSpec(
            (cfg.d_model, cfg.vocab_size), cfg.pdtype, normal_init(0.02),
            ("embed", "vocab"),
        )
    return p


def embed(params, tokens, cfg: ModelConfig):
    x = jnp.take(params["embedding"], tokens, axis=0).astype(cfg.adtype)
    return logical_constraint(x, ("batch", "seq", "act_embed"))


def unembed(params, x, cfg: ModelConfig):
    if cfg.tie_embeddings:
        logits = jnp.einsum(
            "bsd,vd->bsv", x, params["embedding"].astype(x.dtype)
        )
    else:
        logits = jnp.einsum(
            "bsd,dv->bsv", x, params["unembed"].astype(x.dtype)
        )
    return logical_constraint(logits, ("batch", "seq", "act_heads"))
