"""Pure-jnp oracle for the page-table-aware flash decode kernel.

Operates on the RAW paged operands — token-major pools ``(L, n_tok,
Hk*D)`` of every layer, a layer index and a ``(B, pages_per_slot)`` page
table — and reproduces the serving
engine's decode attend (``engine._cache_attend`` at Q=1) op for op over the
gathered heads-major view.  That makes this file the single numerical
contract both the Pallas body and the engine's jnp path are tested against:
the einsum strings, the masking order, the softmax, and the int8
quantize/dot/rescale sequence are copied verbatim from the engine.

No serving imports: the oracle stands alone so the kernel family has no
dependency cycle with ``repro.serving``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # matches repro.core.attention.NEG_INF


def gather_phys(page_ids: jax.Array, page_size: int, seq_len: int) -> jax.Array:
    """Logical->physical gather map: ``(B, pp)`` page ids -> ``(B, S)`` pool
    rows (unmapped ``-1`` pages clamp to row 0; callers mask by position —
    the same convention as ``kv_cache.phys_table``)."""
    pos = jnp.arange(seq_len)
    pid = page_ids[:, pos // page_size]  # (B, S)
    return jnp.where(pid >= 0, pid * page_size + (pos % page_size)[None], 0)


def paged_flash_decode_ref(
    q: jax.Array,  # (B, Hk, g, D) f32 grouped decode query
    k: jax.Array,  # (L, n_tok, Hk*D) bf16/f32, or int8 with k_scale
    v: jax.Array,  # (L, n_tok, Hk*D) bf16/f32, or int8 with v_scale
    page_ids: jax.Array,  # (B, pages_per_slot) int32, -1 = unmapped
    pos: jax.Array,  # (B,) int32 — keys at logical s <= pos[b] are valid
    layer: jax.Array,  # () int32 — the layer of the stack to attend
    *,
    page_size: int,
    k_scale: Optional[jax.Array] = None,  # int8: (L, n_tok, Hk) or (L, B, Hk, S)
    v_scale: Optional[jax.Array] = None,  # int8: (L, n_tok, Hk) or (L, B, Hk, S)
) -> jax.Array:
    """Single-token paged attend of layer ``layer`` -> f32 ``(B, Hk, g, D)``.

    The attended sequence length is ``pages_per_slot * page_size`` (every
    lane a page table row can address); lanes past ``pos[b]`` are masked to
    ``NEG_INF`` exactly like the engine's position mask, so garbage rows
    behind unmapped pages can never contribute probability mass.  Scales
    are token-major pools, translated through the page table like K/V, or
    heads-major per-slot rows indexed by logical position.
    """
    B, Hk, g, D = q.shape
    S = page_ids.shape[1] * page_size
    scale = D**-0.5
    phys = gather_phys(page_ids, page_size, S)  # (B, S)

    def rows(pool):  # layer of a token-major pool -> per-slot (B, S, ...)
        return pool[layer][phys]

    def view(kv):  # (L, n_tok, Hk*D) -> heads-major (B, Hk, S, D)
        return jnp.moveaxis(rows(kv).reshape(B, S, Hk, D), 2, 1)

    qg = q[:, :, :, None, :].astype(jnp.float32)  # (B, Hk, g, Q=1, D)
    valid = (jnp.arange(S)[None, :] <= pos[:, None])[:, None]  # (B, Q=1, S)
    mask = valid[:, None, None]  # (B, 1, 1, Q, S)

    if k_scale is None:
        logits = jnp.einsum(
            "bhgqd,bhsd->bhgqs", qg, view(k).astype(jnp.float32)
        ) * scale
        logits = jnp.where(mask, logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhgqs,bhsd->bhgqd", probs, view(v).astype(jnp.float32))
        return out[:, :, :, 0]

    # int8 path: the engine's A2 (8-bit QK^T) + A3 (8-bit PV) sequence
    q_scale = jnp.maximum(jnp.max(jnp.abs(qg), axis=-1, keepdims=True), 1e-8) / 127.0
    q_q = jnp.clip(jnp.round(qg / q_scale), -127, 127).astype(jnp.int8)
    logits_i = jnp.einsum(
        "bhgqd,bhsd->bhgqs", q_q, view(k), preferred_element_type=jnp.int32
    )
    def scale_rows(sc):  # -> (B, Hk, S)
        return jnp.moveaxis(rows(sc), 2, 1) if sc.ndim == 3 else sc[layer]

    ks, vs = scale_rows(k_scale), scale_rows(v_scale)
    logits = (
        logits_i.astype(jnp.float32)
        * q_scale
        * ks[:, :, None, None, :]
        * scale
    )
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    w = probs * vs[:, :, None, None, :]
    w_scale = jnp.maximum(jnp.max(w, axis=-1, keepdims=True), 1e-20) / 127.0
    w_q = jnp.clip(jnp.round(w / w_scale), 0, 127).astype(jnp.int8)
    out = jnp.einsum(
        "bhgqs,bhsd->bhgqd", w_q, view(v), preferred_element_type=jnp.float32
    )
    out = out * w_scale
    return out[:, :, :, 0]
