"""Shared model building blocks (pure functional JAX, dict-pytree params).

Conventions:
  * params are nested dicts of jnp arrays; leaf names drive sharding rules
    (sharding/rules.py matches on path substrings like 'w_in', 'embed').
  * every `init_*` takes an explicit jax.random key and returns a dict;
    every `*_apply` is side-effect free.
  * matmul dtype follows the param dtype (bf16 on TPU, f32 accumulate on MXU);
    softmax/norm statistics are computed in f32.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.indirect_stream import coalesced_gather


def _dense_init(key, shape, dtype, scale: float | None = None):
    fan_in = shape[0]
    scale = scale if scale is not None else fan_in**-0.5
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_rmsnorm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm_apply(p: dict, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * p["scale"].astype(jnp.float32)).astype(x.dtype)


def init_layernorm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm_apply(p: dict, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps)
    out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding (coalesced-gather backed — the paper's technique at the LM's
# biggest indirect-access site)
# ---------------------------------------------------------------------------


def init_embedding(key, vocab: int, d: int, dtype) -> dict:
    return {"embed": (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)}


def embedding_apply(
    p: dict,
    token_ids: jnp.ndarray,
    *,
    backend: str = "jnp",
    window: int = 256,
    block_rows: int = 8,
) -> jnp.ndarray:
    """(B, S) int32 -> (B, S, D). backend: jnp | coalesced | pallas."""
    if backend == "jnp":
        ids = jax.typeof(token_ids).sharding
        if ids.mesh.empty:
            return p["embed"][token_ids]
        # Under explicit mesh axes the gather's output sharding must be
        # stated: rows follow the token ids' sharding, features replicate.
        return p["embed"].at[token_ids].get(
            out_sharding=NamedSharding(ids.mesh, P(*ids.spec, None))
        )
    return coalesced_gather(
        p["embed"], token_ids, window=window, block_rows=block_rows,
        backend=backend,
    )


def logits_apply(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    """Tied or untied output head: x (..., D) @ embed.T -> (..., vocab)."""
    w = p["embed"] if "embed" in p else p["unembed"]
    return jnp.einsum("...d,vd->...v", x, w).astype(jnp.float32)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, H, hd); positions: (B, S) or (S,)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos = jnp.cos(angles)[..., None, :]  # (B, S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------


def init_attention(
    key, d_model: int, n_heads: int, n_kv_heads: int, head_dim: int, dtype,
    *, qkv_bias: bool = False,
) -> dict:
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (d_model, n_heads * head_dim), dtype),
        "wk": _dense_init(ks[1], (d_model, n_kv_heads * head_dim), dtype),
        "wv": _dense_init(ks[2], (d_model, n_kv_heads * head_dim), dtype),
        "wo": _dense_init(ks[3], (n_heads * head_dim, d_model), dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
    return p


def _sdpa(
    q: jnp.ndarray,  # (B, Sq, H, hd)
    k: jnp.ndarray,  # (B, Sk, Hkv, hd)
    v: jnp.ndarray,  # (B, Sk, Hkv, hd)
    mask: Optional[jnp.ndarray],  # broadcastable to (B, H, Sq, Sk) or None
) -> jnp.ndarray:
    B, Sq, H, hd = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    qg = q.reshape(B, Sq, Hkv, group, hd)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
    scores = scores * (hd**-0.5)
    if mask is not None:
        # mask: (B, 1, Sq, Sk) -> (B, Hkv, group, Sq, Sk) broadcast
        scores = jnp.where(mask[:, :, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(B, Sq, H, v.shape[-1])  # v head dim may differ (MLA)


def causal_mask(sq: int, sk: int, offset: int = 0) -> jnp.ndarray:
    """(1, 1, sq, sk) — True where attendable. `offset` = kv positions already
    in cache before the current query block."""
    qpos = jnp.arange(sq)[:, None] + offset
    kpos = jnp.arange(sk)[None, :]
    return (kpos <= qpos)[None, None]


def attention_apply(
    p: dict,
    x: jnp.ndarray,  # (B, Sq, D)
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    positions: jnp.ndarray,  # (B, Sq) or (Sq,)
    rope_theta: float = 10000.0,
    use_rope: bool = True,
    mask: Optional[jnp.ndarray] = None,
    kv_cache: Optional[tuple] = None,  # (k, v): (B, S_max, Hkv, hd)
    cache_index: Optional[jnp.ndarray] = None,  # scalar: write offset
    kv_override: Optional[tuple] = None,  # cross-attn: precomputed (k, v)
):
    """Returns (out, new_kv_cache). Modes:
      * training/prefill: kv_cache None -> causal over x itself
      * decode: kv_cache given -> write new kv at cache_index, attend to cache
      * cross-attn: kv_override given -> attend to it (no cache update)
    """
    B, Sq, D = x.shape
    q = x @ p["wq"]
    if "bq" in p:
        q = q + p["bq"]
    q = q.reshape(B, Sq, n_heads, head_dim)
    if use_rope:
        q = apply_rope(q, jnp.broadcast_to(positions, (B, Sq)), rope_theta)

    if kv_override is not None:
        k, v = kv_override
        out = _sdpa(q, k, v, mask)
        return out.reshape(B, Sq, -1) @ p["wo"], None

    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bk" in p:
        k, v = k + p["bk"], v + p["bv"]
    k = k.reshape(B, Sq, n_kv_heads, head_dim)
    v = v.reshape(B, Sq, n_kv_heads, head_dim)
    if use_rope:
        k = apply_rope(k, jnp.broadcast_to(positions, (B, Sq)), rope_theta)

    if kv_cache is None:
        if mask is None:
            mask = causal_mask(Sq, Sq)
        out = _sdpa(q, k, v, mask)
        return out.reshape(B, Sq, -1) @ p["wo"], (k, v)

    ck, cv = kv_cache
    ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), cache_index, 1)
    cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), cache_index, 1)
    s_max = ck.shape[1]
    kpos = jnp.arange(s_max)[None, None, None, :]
    qpos = (cache_index + jnp.arange(Sq))[None, None, :, None]
    dec_mask = kpos <= qpos  # causal within the chunk + full history
    out = _sdpa(q, ck, cv, jnp.broadcast_to(dec_mask, (B, 1, Sq, s_max)))
    return out.reshape(B, Sq, -1) @ p["wo"], (ck, cv)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------


def init_mla(key, d_model: int, n_heads: int, mla, dtype) -> dict:
    ks = jax.random.split(key, 6)
    dq = mla.qk_nope_head_dim + mla.qk_rope_head_dim
    return {
        "wq": _dense_init(ks[0], (d_model, n_heads * dq), dtype),
        # compressed KV: d -> kv_lora_rank (the cached latent) + shared k_rope
        "w_dkv": _dense_init(ks[1], (d_model, mla.kv_lora_rank), dtype),
        "w_krope": _dense_init(ks[2], (d_model, mla.qk_rope_head_dim), dtype),
        "kv_norm": init_rmsnorm(mla.kv_lora_rank, dtype),
        # up-projections from the latent
        "w_uk": _dense_init(
            ks[3], (mla.kv_lora_rank, n_heads * mla.qk_nope_head_dim), dtype
        ),
        "w_uv": _dense_init(
            ks[4], (mla.kv_lora_rank, n_heads * mla.v_head_dim), dtype
        ),
        "wo": _dense_init(ks[5], (n_heads * mla.v_head_dim, d_model), dtype),
    }


def mla_apply(
    p: dict,
    x: jnp.ndarray,
    *,
    n_heads: int,
    mla,
    positions: jnp.ndarray,
    rope_theta: float,
    mask: Optional[jnp.ndarray] = None,
    latent_cache: Optional[tuple] = None,  # (c_kv (B,S,r), k_rope (B,S,dr))
    cache_index: Optional[jnp.ndarray] = None,
):
    """DeepSeek-V2 MLA. The decode cache holds only the compressed latent
    (kv_lora_rank) + shared rope key — the paper-relevant property (the cache
    is the 'narrow element' stream; its gather is block-coalesced)."""
    B, Sq, D = x.shape
    dn, dr, dv = mla.qk_nope_head_dim, mla.qk_rope_head_dim, mla.v_head_dim
    q = (x @ p["wq"]).reshape(B, Sq, n_heads, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, jnp.broadcast_to(positions, (B, Sq)), rope_theta)

    c_kv = rmsnorm_apply(p["kv_norm"], x @ p["w_dkv"])  # (B, Sq, r)
    k_rope = apply_rope(
        (x @ p["w_krope"])[:, :, None, :],
        jnp.broadcast_to(positions, (B, Sq)),
        rope_theta,
    )[:, :, 0]  # (B, Sq, dr) single shared rope head

    if latent_cache is not None:
        cc, cr = latent_cache
        cc = jax.lax.dynamic_update_slice_in_dim(
            cc, c_kv.astype(cc.dtype), cache_index, 1
        )
        cr = jax.lax.dynamic_update_slice_in_dim(
            cr, k_rope.astype(cr.dtype), cache_index, 1
        )
        c_all, r_all = cc, cr
        sk = cc.shape[1]
        qpos = (cache_index + jnp.arange(Sq))[None, None, :, None]
        mask = jnp.arange(sk)[None, None, None, :] <= qpos
        mask = jnp.broadcast_to(mask, (B, 1, Sq, sk))
        new_cache = (cc, cr)
    else:
        c_all, r_all = c_kv, k_rope
        sk = Sq
        if mask is None:
            mask = causal_mask(Sq, sk)
        new_cache = (c_kv, k_rope)

    k_nope = (c_all @ p["w_uk"]).reshape(B, sk, n_heads, dn)
    v = (c_all @ p["w_uv"]).reshape(B, sk, n_heads, dv)
    k_rope_b = jnp.broadcast_to(r_all[:, :, None, :], (B, sk, n_heads, dr))
    k = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = _sdpa(q_full, k, v, mask)
    return out.reshape(B, Sq, -1) @ p["wo"], new_cache


# ---------------------------------------------------------------------------
# FFN
# ---------------------------------------------------------------------------


def init_ffn(key, d_model: int, d_ff: int, dtype, act: str = "silu") -> dict:
    ks = jax.random.split(key, 3)
    p = {
        "w_in": _dense_init(ks[0], (d_model, d_ff), dtype),
        "w_out": _dense_init(ks[1], (d_ff, d_model), dtype),
    }
    if act == "silu":  # SwiGLU
        p["w_gate"] = _dense_init(ks[2], (d_model, d_ff), dtype)
    else:
        p["b_in"] = jnp.zeros((d_ff,), dtype)
        p["b_out"] = jnp.zeros((d_model,), dtype)
    return p


def ffn_apply(p: dict, x: jnp.ndarray, act: str = "silu") -> jnp.ndarray:
    if act == "silu":
        return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_in"])) @ p["w_out"]
    h = jax.nn.gelu((x @ p["w_in"]) + p["b_in"])
    return (h @ p["w_out"]) + p["b_out"]
