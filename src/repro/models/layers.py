"""Shared neural layers (functional, pytree-parameterized, shard-annotated).

Every init function returns ``(params, specs)`` where ``specs`` mirrors the
param pytree with tuples of *logical* axis names consumed by
``repro.distributed.sharding.ShardingRules``.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed import sharding as sh


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


# --------------------------------------------------------------------------
# Initializers
# --------------------------------------------------------------------------


def dense_init(key, in_dim: int, out_dim: int, dtype, scale: Optional[float] = None):
    scale = (1.0 / math.sqrt(in_dim)) if scale is None else scale
    return jax.random.normal(key, (in_dim, out_dim), dtype) * jnp.asarray(
        scale, dtype
    )


def embed_init(key, vocab: int, dim: int, dtype):
    return jax.random.normal(key, (vocab, dim), dtype) * jnp.asarray(0.02, dtype)


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dt)


def layer_norm(
    x: jax.Array, weight: jax.Array, bias: jax.Array, eps: float = 1e-5
) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def apply_norm(x, params, kind: str):
    if kind == "rms":
        return rms_norm(x, params["scale"])
    return layer_norm(x, params["scale"], params["bias"])


def norm_specs(kind: str):
    if kind == "rms":
        return {"scale": (sh.D_MODEL,)}
    return {"scale": (sh.D_MODEL,), "bias": (sh.D_MODEL,)}


def norm_init(dim: int, kind: str, dtype):
    if kind == "rms":
        return {"scale": jnp.zeros((dim,), dtype)}, norm_specs(kind)
    return (
        {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)},
        norm_specs(kind),
    )


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(
    x: jax.Array,  # (B, S, H, D)
    positions: jax.Array,  # (B, S) int32
    theta: float,
) -> jax.Array:
    freqs = rope_frequencies(x.shape[-1], theta)  # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, D/2)
    sin = jnp.sin(angles)[:, :, None, :]
    cos = jnp.cos(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(num_pos: int, dim: int) -> jax.Array:
    """Extended sinusoidal table (whisper decoder beyond 448 — DESIGN.md §4)."""
    pos = jnp.arange(num_pos, dtype=jnp.float32)[:, None]
    div = jnp.exp(
        jnp.arange(0, dim, 2, dtype=jnp.float32) * (-math.log(10000.0) / dim)
    )
    pe = jnp.zeros((num_pos, dim), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


# --------------------------------------------------------------------------
# Weight contraction (raw leaf or quantized serve record)
# --------------------------------------------------------------------------


def wdot(x: jax.Array, w, preferred_element_type=None) -> jax.Array:
    """``x @ w`` for a raw weight leaf OR a serve-time quantized record.

    ``repro.serving.weights.prepare_serve_params`` replaces projection
    leaves with ``{"q": int8 (in, out), "scale": f32 (out,)}`` records when
    ``weight_format`` is int8/bstc; this helper dequantizes the record to
    the dense reconstruction (the parity oracle) and contracts in the
    activation dtype.  Raw arrays take the plain matmul — the bf16 default
    path is byte-for-byte the old ``x @ w``.  ``preferred_element_type``
    sets the product's dtype (the tensor-parallel serving path keeps its
    partial sums in float32).
    """
    if isinstance(w, dict) and "q" in w:
        dq = w["q"].astype(jnp.float32) * w["scale"][..., None, :].astype(
            jnp.float32
        )
        w = dq.astype(x.dtype)
    if preferred_element_type is None:
        return x @ w
    return jnp.matmul(x, w, preferred_element_type=preferred_element_type)


# --------------------------------------------------------------------------
# Activations / MLP
# --------------------------------------------------------------------------


def glu_act(kind: str, gate: jax.Array) -> jax.Array:
    if kind == "swiglu":
        return jax.nn.silu(gate)
    if kind == "geglu":
        return jax.nn.gelu(gate, approximate=True)
    raise ValueError(kind)


def mlp_specs(activation: str):
    if activation in ("swiglu", "geglu"):
        return {
            "gate": (sh.D_MODEL, sh.FF),
            "up": (sh.D_MODEL, sh.FF),
            "down": (sh.FF, sh.D_MODEL),
        }
    return {
        "up": (sh.D_MODEL, sh.FF),
        "up_b": (sh.FF,),
        "down": (sh.FF, sh.D_MODEL),
        "down_b": (sh.D_MODEL,),
    }


def mlp_init(key, d_model: int, d_ff: int, activation: str, dtype):
    ks = jax.random.split(key, 3)
    if activation in ("swiglu", "geglu"):
        params = {
            "gate": dense_init(ks[0], d_model, d_ff, dtype),
            "up": dense_init(ks[1], d_model, d_ff, dtype),
            "down": dense_init(ks[2], d_ff, d_model, dtype),
        }
    else:  # plain gelu MLP (whisper)
        params = {
            "up": dense_init(ks[0], d_model, d_ff, dtype),
            "up_b": jnp.zeros((d_ff,), dtype),
            "down": dense_init(ks[1], d_ff, d_model, dtype),
            "down_b": jnp.zeros((d_model,), dtype),
        }
    return params, mlp_specs(activation)


def mlp_apply(params, x: jax.Array, activation: str, down=wdot) -> jax.Array:
    """Dense MLP; ``down(h, w)`` contracts the hidden activations with the
    down projection (the tensor-parallel serving path passes its
    row-parallel product and all-reduce)."""
    if activation in ("swiglu", "geglu"):
        gate = wdot(x, params["gate"])
        up = wdot(x, params["up"])
        return down(glu_act(activation, gate) * up, params["down"])
    h = jax.nn.gelu(wdot(x, params["up"]) + params["up_b"], approximate=True)
    return down(h, params["down"]) + params["down_b"]


# --------------------------------------------------------------------------
# Attention projections
# --------------------------------------------------------------------------


def attention_specs(qk_norm: bool = False):
    specs = {
        "wq": (sh.D_MODEL, sh.HEADS),
        "wk": (sh.D_MODEL, sh.KV_HEADS),
        "wv": (sh.D_MODEL, sh.KV_HEADS),
        "wo": (sh.HEADS, sh.D_MODEL),
    }
    if qk_norm:
        specs["q_norm"] = {"scale": (None,)}
        specs["k_norm"] = {"scale": (None,)}
    return specs


def attention_init(
    key,
    d_model: int,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    dtype,
    qk_norm: bool = False,
    norm_kind: str = "rms",
):
    ks = jax.random.split(key, 4)
    params = {
        "wq": dense_init(ks[0], d_model, num_heads * head_dim, dtype),
        "wk": dense_init(ks[1], d_model, num_kv_heads * head_dim, dtype),
        "wv": dense_init(ks[2], d_model, num_kv_heads * head_dim, dtype),
        "wo": dense_init(ks[3], num_heads * head_dim, d_model, dtype),
    }
    if qk_norm:
        params["q_norm"] = {"scale": jnp.zeros((head_dim,), dtype)}
        params["k_norm"] = {"scale": jnp.zeros((head_dim,), dtype)}
    return params, attention_specs(qk_norm)


def qkv_project(
    params,
    x: jax.Array,  # (B, S, D)
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    positions: Optional[jax.Array],
    rope_theta: float,
    qk_norm: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, S, _ = x.shape
    q = wdot(x, params["wq"]).reshape(B, S, num_heads, head_dim)
    k = wdot(x, params["wk"]).reshape(B, S, num_kv_heads, head_dim)
    v = wdot(x, params["wv"]).reshape(B, S, num_kv_heads, head_dim)
    if qk_norm:
        q = rms_norm(q, params["q_norm"]["scale"])
        k = rms_norm(k, params["k_norm"]["scale"])
    if positions is not None:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    return q, k, v
