"""Pure-jnp oracle for the fused two-phase BGPP paged decode.

A verbatim port of the serving engine's pipeline onto RAW pool operands —
``_bgpp_quant_query`` -> ``_bgpp_topk_indices`` (progressive MSB-first
plane scoring with early termination) -> compacted survivor gather ->
``_bgpp_formal_attend`` (exact int8 A2/A3 formal compute) — with the
paged gathers (``paged_plane`` / ``paged_sign`` / ``paged_plane_rows`` /
``paged_topk_entry``) inlined as plain ``take``/``vmap`` so the family has
no import edge into ``repro.serving``.  Every float op keeps the engine's
order, so the selected candidate sets AND the final logits are
bit-identical to the engine's jnp path.  Phase-1 scores are integer-exact
plane sums, and every round's survivors are kept in ascending logical
order, so each ``top_k`` breaks ties toward the lowest logical index: the
selection is a function of the scores alone.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import bgpp as bgpp_mod, bitslice
from repro.kernels.paged_flash_decode.ref import gather_phys

NEG_INF = -1e30  # matches repro.core.attention.NEG_INF
NBITS = bitslice.WEIGHT_MAG_BITS  # 7 magnitude planes + sign


def _quant_query(q: jax.Array) -> jax.Array:
    """(B, Hk, g, D) f32 raw query -> quantized+MSB-truncated f32 (engine
    ``_bgpp_quant_query`` on the already-grouped layout)."""
    qg = q.astype(jnp.float32)
    dq = jnp.maximum(jnp.max(jnp.abs(qg), axis=-1, keepdims=True), 1e-8) / 127.0
    q_int = jnp.clip(jnp.round(qg / dq), -127, 127).astype(jnp.int32)
    q_int = bgpp_mod._truncate_query(
        q_int, NBITS, bgpp_mod.DEFAULT_QUERY_BITS
    )
    return q_int.astype(jnp.float32)


def _rows_per_head(pool: jax.Array, rows: jax.Array, planar: bool):
    """Per-(slot, head) compacted pool gather (``kvc._gather_rows_per_head``):
    pool ``(n_tok, Hk, ...)`` (planar: leading NBITS), rows ``(B, Hk, k)``
    -> ``(B, Hk, k, ...)`` (planar: leading NBITS)."""
    heads = jnp.arange(rows.shape[1])
    if planar:
        return jax.vmap(
            lambda r, h: pool[:, r, h], in_axes=(1, 0), out_axes=2
        )(rows, heads)
    return jax.vmap(
        lambda r, h: pool[r, h], in_axes=(1, 0), out_axes=1
    )(rows, heads)


def _topk_indices(qf, plane0, sign_full, plane_at, valid,
                  rounds: int, k_max: int, survivors: Tuple[int, ...]):
    """Engine ``_bgpp_topk_indices`` verbatim, with the plan passed in."""
    B, Hk, g, Dh = qf.shape
    S = valid.shape[1]

    def plane_scores(plane_bits, sign_bits, qf_):
        signed = jnp.where(sign_bits.astype(bool), -1.0, 1.0) * plane_bits
        return jnp.einsum("bhgd,bhsd->bhgs", qf_, signed)

    p0 = NBITS - 1
    plane = bitslice.unpack_bits(plane0, axis=-1).astype(jnp.float32)
    sign = bitslice.unpack_bits(sign_full, axis=-1)
    partial = plane_scores(plane, sign, qf) * float(2**p0)
    score_h = jnp.max(partial, axis=2)
    score_h = jnp.where(valid[:, None, :], score_h, NEG_INF)

    cur_idx = None
    for r in range(1, rounds):
        k_r = survivors[r]
        li = jnp.sort(jax.lax.top_k(score_h, k_r)[1], axis=-1)
        cur_idx = li if cur_idx is None else jnp.take_along_axis(cur_idx, li, axis=2)
        partial = jnp.take_along_axis(partial, li[:, :, None, :], axis=3)
        p_r = NBITS - 1 - r
        plane_g = bitslice.unpack_bits(
            plane_at(p_r, cur_idx), axis=-1
        ).astype(jnp.float32)
        sign_g = bitslice.unpack_bits(
            jnp.take_along_axis(sign_full, cur_idx[..., None], axis=2), axis=-1
        )
        partial = partial + plane_scores(plane_g, sign_g, qf) * float(2**p_r)
        score_h = jnp.max(partial, axis=2)
        score_h = jnp.where(
            jnp.take_along_axis(
                jnp.broadcast_to(valid[:, None, :], (B, Hk, S)), cur_idx, axis=2
            ),
            score_h, NEG_INF,
        )

    li = jnp.sort(jax.lax.top_k(score_h, k_max)[1], axis=-1)
    idx = li if cur_idx is None else jnp.take_along_axis(cur_idx, li, axis=2)
    idx_valid = jnp.take_along_axis(
        jnp.broadcast_to(valid[:, None, :], (B, Hk, S)), idx, axis=2
    )
    return idx, idx_valid


def _formal_attend(q, k_q, k_scale, v, v_scale, idx_valid, scale):
    """Engine ``_bgpp_formal_attend``'s int8 attend (``_cache_attend`` with
    fmt=int8, Q=1, valid=ones, head_mask=idx_valid) on the grouped query."""
    qg = q[:, :, :, None, :].astype(jnp.float32)  # (B, Hk, g, Q=1, D)
    # engine mask: all-ones lane validity AND the per-(b,h) candidate mask
    mask = idx_valid[:, :, None, None, :]  # (B, Hk, 1, Q=1, k)
    q_scale = jnp.maximum(jnp.max(jnp.abs(qg), axis=-1, keepdims=True), 1e-8) / 127.0
    q_q = jnp.clip(jnp.round(qg / q_scale), -127, 127).astype(jnp.int8)
    logits_i = jnp.einsum(
        "bhgqd,bhsd->bhgqs", q_q, k_q, preferred_element_type=jnp.int32
    )
    logits = (
        logits_i.astype(jnp.float32)
        * q_scale
        * k_scale[:, :, None, None, :]
        * scale
    )
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    w = probs * v_scale[:, :, None, None, :]
    w_scale = jnp.maximum(jnp.max(w, axis=-1, keepdims=True), 1e-20) / 127.0
    w_q = jnp.clip(jnp.round(w / w_scale), 0, 127).astype(jnp.int8)
    out = jnp.einsum(
        "bhgqs,bhsd->bhgqd", w_q, v, preferred_element_type=jnp.float32
    )
    return (out * w_scale)[:, :, :, 0]


def bgpp_paged_attend_ref(
    q: jax.Array,  # (B, Hk, g, D) f32 RAW grouped decode query
    k_planes: jax.Array,  # (NBITS, n_tok, Hk, D/8) uint8 packed planes
    k_sign: jax.Array,  # (n_tok, Hk, D/8) uint8 packed sign plane
    k_scale: jax.Array,  # (n_tok, Hk) f32
    v: jax.Array,  # (n_tok, Hk, D) int8
    v_scale: jax.Array,  # (n_tok, Hk) f32
    page_ids: jax.Array,  # (B, pages_per_slot) int32, -1 = unmapped
    pos: jax.Array,  # (B,) int32 — keys at logical s <= pos[b] are valid
    *,
    page_size: int,
    rounds: int,
    k_max: int,
    survivors: Tuple[int, ...],
) -> jax.Array:
    """Fused two-phase BGPP decode -> f32 ``(B, Hk, g, D)``.

    The ``(rounds, k_max, survivors)`` plan comes from the caller
    (``kv_cache.bgpp_decode_plan`` in the serving engine), so the kernel
    reads exactly the bytes the kv-read counter prices.
    """
    B, Hk, g, D = q.shape
    S = survivors[0]  # the logical context; the pages may span more lanes
    phys = gather_phys(page_ids, page_size, S)  # (B, S) pool rows
    valid = jnp.arange(S)[None, :] <= pos[:, None]  # (B, S)
    qf = _quant_query(q)

    def heads_major(pool, plane=None):
        # paged_plane / paged_sign: phys (B,S) -> heads-major (B,Hk,S,D/8)
        a = pool if plane is None else pool[plane]
        return jnp.moveaxis(a[phys], 2, 1)

    def plane_at(p, idx):
        rows = jnp.take_along_axis(
            phys, idx.reshape(B, Hk * idx.shape[2]), axis=1
        ).reshape(idx.shape)
        return _rows_per_head(k_planes[p], rows, False)

    idx, idx_valid = _topk_indices(
        qf, heads_major(k_planes, NBITS - 1), heads_major(k_sign), plane_at,
        valid, rounds, k_max, survivors,
    )

    rows = jnp.take_along_axis(
        phys, idx.reshape(B, Hk * k_max), axis=1
    ).reshape(B, Hk, k_max)
    planes_g = _rows_per_head(k_planes, rows, True)  # (NBITS, B, Hk, k, D/8)
    sign_g = _rows_per_head(k_sign, rows, False)
    k_q = bitslice.from_sign_magnitude(
        bitslice.unpack_bits(sign_g, axis=-1),
        bitslice.from_bitplanes(bitslice.unpack_bits(planes_g, axis=-1)),
    ).astype(jnp.int8)
    return _formal_attend(
        q, k_q,
        _rows_per_head(k_scale, rows, False),
        _rows_per_head(v, rows, False),
        _rows_per_head(v_scale, rows, False),
        idx_valid, D**-0.5,
    )
