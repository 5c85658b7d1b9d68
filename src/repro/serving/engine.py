"""Serving engine: prefill + single-token decode (``serve_step``) for every
family, with int8 KV, ring-buffered local windows, and the MCBP BGPP sparse
path.

``make_serve_step(cfg, layout, rules)`` returns the pure function the
dry-run lowers for the decode_32k / long_500k cells:

    serve_step(params, cache, tokens (B,1)) -> (logits (B,1,V), cache')

Positions are per slot: ``cache["pos"]`` is a ``(B,)`` vector, and every
decode path (RoPE, ring-buffer slots, causal/window masks, BGPP round-0
masking) indexes it per batch row, so staggered requests share one batch
(continuous batching).  ``prefill`` builds a fresh whole-batch cache;
``prefill_into_slot`` admits one prompt into a single slot of a live cache.

Decode loops over layers in python (tiny per-layer op count; heterogeneous
caches), indexing the stacked parameter pytrees with static layer ids.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import attention, bgpp as bgpp_mod, bitslice
from repro.distributed import sharding as sh
from repro.models import layers, mamba2, moe, transformer
from repro.serving import kernel_decode, kv_cache as kvc, sharded as shd
from repro.serving import weights as swt

Tree = Dict[str, Any]
NEG_INF = attention.NEG_INF


def _row_parallel(x, w, rules, axis=sh.HEADS, scope="tp.attn_out"):
    """``x @ w`` for a row-parallel projection: ``x`` stays sharded over
    ``"model"`` on its last (logical ``axis``) axis, as ``w`` is on its
    rows (``serving.sharded``), so every device multiplies its own slice,
    and one all-reduce in :data:`kv_cache.TP_SUM_DTYPE` (named ``scope`` in
    the device trace) sums the partial outputs, replicated over
    ``"model"``.  The sum is rounded once to the dtype ``x @ w`` has, as
    one device's dot rounds its accumulator.  On a ``"model"`` axis of size
    1 nothing is summed across devices: the product is that one dot, in
    ``x @ w``'s dtype (a float32 output there would only write a prefill
    chunk's bf16 rows at twice the bytes)."""
    summed = shd.mesh_shape(rules)[1] > 1
    # a quantized record dequantizes to x's dtype (layers.wdot)
    dtype = x.dtype if isinstance(w, dict) else jnp.result_type(x, w)
    x = sh.constrain(x, rules, (sh.BATCH, None, axis))
    with jax.named_scope(scope):
        y = layers.wdot(x, w, preferred_element_type=(
            kvc.TP_SUM_DTYPE if summed else None))
        y = sh.constrain(y, rules, (sh.BATCH, None, None))
    return y.astype(dtype)


# --------------------------------------------------------------------------
# attention decode over the cache stacks
# --------------------------------------------------------------------------


def _split_heads(x, B, H, Dh):
    return x.reshape(B, H, Dh)


def _cache_attend(
    q,  # (B, Q, Hq, Dh) — Q query tokens per batch row
    entry: Tree,  # this layer's heads-major (B,Hk,S,D) view (slot_entry)
    valid,  # (B, Q, S) bool per-query key masks
    cfg,
    fmt: str,
    head_mask=None,  # (B, Hk, S) BGPP alive sets
):
    """Attention over the heads-major cache for Q query tokens per row.

    The entry is one layer's heads-major view (``kv_cache.slot_entry`` or
    ``kv_cache.paged_entry``; §Perf iteration A1); the int8 format runs
    the paper-faithful 8-bit QK^T (A2) and 8-bit PV (A3) as int8 MXU dots,
    so the cache is consumed directly with no dequantized copies.  Decode
    calls it with Q=1; chunked prefill with Q=chunk — the key axis is the
    full ``S`` stack either way, so per-query reductions are shape-stable
    (the chunked-admission bit-exactness contract).  Returns f32
    ``(B, Q, Hq, Dh)``.
    """
    B, Q, Hq, Dh = q.shape
    # GQA group size from the config RATIO, head count from the operand:
    # under shard_map (the paged BGPP decode's "model" routing) q carries
    # only this device's head shard, and the ratio is shard-invariant
    if cfg.num_heads % cfg.num_kv_heads:
        raise ValueError(
            f"_cache_attend: num_heads={cfg.num_heads} not a multiple of "
            f"num_kv_heads={cfg.num_kv_heads} — GQA grouping needs an "
            f"integral ratio"
        )
    g = cfg.num_heads // cfg.num_kv_heads
    if Hq % g:
        raise ValueError(
            f"_cache_attend: operand carries Hq={Hq} heads, not a multiple "
            f"of the GQA group size g={g} — a head shard must keep whole "
            f"(kv-head, group) blocks together"
        )
    Hk = Hq // g
    scale = Dh**-0.5
    qg = q.reshape(B, Q, Hk, g, Dh).transpose(0, 2, 3, 1, 4).astype(jnp.float32)

    mask = valid[:, None, None]  # (B, 1, 1, Q, S)
    if head_mask is not None:
        mask = mask & head_mask[:, :, None, None, :]

    if fmt == "bf16":
        logits = jnp.einsum(
            "bhgqd,bhsd->bhgqs", qg, entry["k"].astype(jnp.float32)
        ) * scale
        logits = jnp.where(mask, logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhgqs,bhsd->bhgqd", probs, entry["v"].astype(jnp.float32))
        return out.transpose(0, 3, 1, 2, 4).reshape(B, Q, Hq, Dh)

    # paper §2.2 formal compute, 8-bit QK^T: quantize q per (b,h,g,q) row
    # and run an int8×int8 MXU dot with int32 accumulation — no dequantized
    # f32 copy of the key cache is ever materialized (§Perf iteration A2).
    q_scale = jnp.maximum(jnp.max(jnp.abs(qg), axis=-1, keepdims=True), 1e-8) / 127.0
    q_q = jnp.clip(jnp.round(qg / q_scale), -127, 127).astype(jnp.int8)
    logits_i = jnp.einsum(
        "bhgqd,bhsd->bhgqs", q_q, entry["k"], preferred_element_type=jnp.int32
    )
    logits = (
        logits_i.astype(jnp.float32)
        * q_scale
        * entry["k_scale"][:, :, None, None, :]
        * scale
    )
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)

    # paper's 8-bit PV (§Perf iteration A3): fold the per-key v_scale into
    # the probs, quantize the weighted probs per (b,h,g,q) row to int8, and
    # keep V int8 in the dot (f32 accumulation on the MXU).
    w = probs * entry["v_scale"][:, :, None, None, :]  # (B,Hk,g,Q,S)
    w_scale = jnp.maximum(jnp.max(w, axis=-1, keepdims=True), 1e-20) / 127.0
    w_q = jnp.clip(jnp.round(w / w_scale), 0, 127).astype(jnp.int8)
    out = jnp.einsum(
        "bhgqs,bhsd->bhgqd", w_q, entry["v"], preferred_element_type=jnp.float32
    )
    out = out * w_scale
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Q, Hq, Dh)


def _decode_attend(q, entry, valid, cfg, fmt, head_mask=None):
    """Single-token wrapper: q (B, Hq, Dh), valid (B, S) -> (B, Hq, Dh)."""
    return _cache_attend(
        q[:, None], entry, valid[:, None], cfg, fmt, head_mask=head_mask
    )[:, 0]


def _bgpp_quant_query(q, cfg):
    """Quantize + MSB-truncate the decode query for bit-plane scoring.

    q ``(B, Hq, Dh)`` -> f32 ``(B, Hk, g, Dh)`` (paper: 4-bit MSB query
    precompute, shared by the slot and paged BGPP decode paths).
    """
    B, Hq, Dh = q.shape
    # ratio from the config, count from the operand (shard_map-local safe)
    if cfg.num_heads % cfg.num_kv_heads:
        raise ValueError(
            f"_bgpp_quant_query: num_heads={cfg.num_heads} not a multiple "
            f"of num_kv_heads={cfg.num_kv_heads} — GQA grouping needs an "
            f"integral ratio"
        )
    g = cfg.num_heads // cfg.num_kv_heads
    if Hq % g:
        raise ValueError(
            f"_bgpp_quant_query: operand carries Hq={Hq} heads, not a "
            f"multiple of the GQA group size g={g} — a head shard must "
            f"keep whole (kv-head, group) blocks together"
        )
    Hk = Hq // g
    qg = q.reshape(B, Hk, g, Dh).astype(jnp.float32)
    dq = jnp.maximum(jnp.max(jnp.abs(qg), axis=-1, keepdims=True), 1e-8) / 127.0
    q_int = jnp.clip(jnp.round(qg / dq), -127, 127).astype(jnp.int32)
    q_int = bgpp_mod._truncate_query(q_int, kvc.NBITS, bgpp_mod.DEFAULT_QUERY_BITS)
    return q_int.astype(jnp.float32)  # (B,Hk,g,D)


def _bgpp_topk_indices(qf, plane0, sign_full, plane_at, valid, cfg):
    """Progressive MSB-first top-k prediction (paper §3.3 early termination)
    — phase 1 of BGPP decode, shared by the slot and paged layouts.

    Round 0 scores the magnitude MSB plane of every valid key; each later
    round fetches the next plane for the surviving half only — a
    static-shape realization of the paper's early termination whose HBM
    traffic is the packed bytes of survivors, not the whole cache.

    qf: ``(B, Hk, g, D)`` quantized query (:func:`_bgpp_quant_query`);
    plane0: ``(B, Hk, S, D/8)`` packed MSB plane of EVERY key; sign_full:
    ``(B, Hk, S, D/8)``; ``plane_at(p, idx)``: packed plane ``p`` at
    logical indices ``(B, Hk, k)`` -> ``(B, Hk, k, D/8)`` — the slot
    layout takes from its dense row, the paged layout gathers survivor
    pool rows directly, and both return identical VALUES, which is what
    keeps the selected sets (and hence the final logits) identical across
    layouts.

    Returns ``(idx (B, Hk, k_max) logical ids, idx_valid (B, Hk, k_max))``
    with ``k_max = ceil(bgpp_keep_ratio * S)``.
    """
    B, Hk, g, Dh = qf.shape
    S = valid.shape[1]
    # the plan IS the accounting: the same tuple prices decode_read_bytes
    rounds, k_max, survivors = kvc.bgpp_decode_plan(S, cfg)

    def plane_scores(plane_bits, sign_bits, qf_):
        """signed plane contribution: (..., S', D) bits -> (B,Hk,g,S')."""
        signed = jnp.where(sign_bits.astype(bool), -1.0, 1.0) * plane_bits
        return jnp.einsum("bhgd,bhsd->bhgs", qf_, signed)

    # ---- round 0: MSB plane of every valid key ---------------------------
    p0 = kvc.NBITS - 1
    plane = bitslice.unpack_bits(plane0, axis=-1).astype(jnp.float32)
    sign = bitslice.unpack_bits(sign_full, axis=-1)
    partial = plane_scores(plane, sign, qf) * float(2**p0)  # (B,Hk,g,S)
    score_h = jnp.max(partial, axis=2)  # GQA union
    score_h = jnp.where(valid[:, None, :], score_h, NEG_INF)

    # ---- progressive rounds: halve the candidate set, gather next plane --
    # pure-gather formulation: cur_idx tracks the global ids of survivors;
    # scores/partials shrink with the set, nothing is scattered back
    cur_idx = None  # None = all S keys
    for r in range(1, rounds):
        k_r = survivors[r]
        # local ids in the current set, kept in ascending logical order so
        # every round breaks score ties toward the lowest logical index
        li = jnp.sort(jax.lax.top_k(score_h, k_r)[1], axis=-1)
        cur_idx = li if cur_idx is None else jnp.take_along_axis(cur_idx, li, axis=2)
        partial = jnp.take_along_axis(partial, li[:, :, None, :], axis=3)
        p_r = kvc.NBITS - 1 - r
        plane_g = bitslice.unpack_bits(
            plane_at(p_r, cur_idx), axis=-1
        ).astype(jnp.float32)  # (B,Hk,k_r,D)
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

    # ---- the final k_max candidate set -----------------------------------
    li = jnp.sort(jax.lax.top_k(score_h, k_max)[1], axis=-1)
    idx = li if cur_idx is None else jnp.take_along_axis(cur_idx, li, axis=2)
    idx_valid = jnp.take_along_axis(
        jnp.broadcast_to(valid[:, None, :], (B, Hk, S)), idx, axis=2
    )
    return idx, idx_valid


def _bgpp_formal_attend(q, gathered, idx_valid, cfg):
    """Phase 2 of BGPP decode: exact int8 formal compute (A2/A3) over the
    compacted candidate set.

    gathered: ``{k_planes (NBITS, B, Hk, k, D/8), k_sign (B, Hk, k, D/8),
    k_scale (B, Hk, k), v (B, Hk, k, D), v_scale (B, Hk, k)}`` — the
    surviving tokens' full-precision rows, from either layout's gather.
    ``idx_valid`` masks candidate lanes that top-k filled from invalid
    cache positions (their gathered values are garbage, but NEG_INF logits
    zero their probability mass exactly, so they cannot leak into the
    output).
    """
    B = q.shape[0]
    k_max = idx_valid.shape[-1]
    k_q = kvc.bitplanes_to_k(
        gathered["k_planes"], gathered["k_sign"]
    ).astype(jnp.int8)  # (B,Hk,k,D)
    entry = {
        "k": k_q,
        "k_scale": gathered["k_scale"],
        "v": gathered["v"],
        "v_scale": gathered["v_scale"],
    }
    # int8 formal compute with per-(b,h) candidate masks
    return _decode_attend(
        q, entry,
        valid=jnp.ones((B, k_max), bool), cfg=cfg, fmt="int8",
        head_mask=idx_valid,
    )


def _bgpp_decode_attend(q, entry, valid, cfg):
    """BGPP progressive decode over a FULL heads-major entry (paper §3.3 +
    §4.5, TPU-adapted; §Perf iteration C1) — the slot-layout path and the
    reference the two-phase paged path is tested bit-identical against.

    The final candidate set (k_max = keep_ratio·S) is gathered once at
    full precision and consumed by the exact int8 formal compute (A2/A3).

    entry: heads-major bgpp stack slices — k_planes (NBITS,B,Hk,S,D/8),
    k_sign/(B,Hk,S,D/8), k_scale/v_scale (B,Hk,S), v (B,Hk,S,D).
    q: (B, Hq, Dh).
    """
    qf = _bgpp_quant_query(q, cfg)
    idx, idx_valid = _bgpp_topk_indices(
        qf, entry["k_planes"][kvc.NBITS - 1], entry["k_sign"],
        lambda p, i: jnp.take_along_axis(
            entry["k_planes"][p], i[..., None], axis=2
        ),
        valid, cfg,
    )
    take = lambda x: jnp.take_along_axis(x, idx[..., None], axis=2)
    gathered = {
        "k_planes": jnp.stack(
            [take(entry["k_planes"][pp]) for pp in range(kvc.NBITS)], axis=0
        ),  # (NBITS,B,Hk,k,D/8)
        "k_sign": take(entry["k_sign"]),
        "k_scale": jnp.take_along_axis(entry["k_scale"], idx, axis=2),
        "v": take(entry["v"]),
        "v_scale": jnp.take_along_axis(entry["v_scale"], idx, axis=2),
    }
    return _bgpp_formal_attend(q, gathered, idx_valid, cfg)


def _bgpp_paged_decode_attend(q, store, gi, phys, valid, cfg):
    """Two-phase BGPP decode on the paged pool — the access-reduced path.

    Unlike every other paged attend, this never materializes the slot's
    full row (:func:`repro.serving.kv_cache.paged_entry`): phase 1 gathers
    only the cheap bit-slice planes — the MSB magnitude plane and the sign
    plane at full width, then one further plane per progressive round for
    the surviving candidates only — and runs the shared top-k prediction;
    phase 2 translates the surviving logical indices through the page
    table and gathers ONLY those ``ceil(keep_ratio·S)`` tokens'
    full-precision rows into a compacted ``(B, Hk, K, ...)`` buffer for
    the exact int8 formal compute.  Selection sees the same plane values
    as the full-entry path, so the logits are bit-identical to
    :func:`_bgpp_decode_attend` on the gathered view
    (tests/test_bgpp_gather.py) — the reads shrink, the math doesn't.
    """
    qf = _bgpp_quant_query(q, cfg)
    idx, idx_valid = _bgpp_topk_indices(
        qf,
        kvc.paged_plane(store, gi, kvc.NBITS - 1, phys),
        kvc.paged_sign(store, gi, phys),
        lambda p, i: kvc.paged_plane_rows(
            store, gi, p, kvc.paged_rows_at(phys, i)
        ),
        valid, cfg,
    )
    gathered = kvc.paged_topk_entry(store, gi, kvc.paged_rows_at(phys, idx))
    # materialize the compacted survivor rows before the formal compute so
    # the pool gather can't fuse into the attend (sharding-stable lowering,
    # same reasoning as the dense paged_entry barrier in the decode layer)
    gathered = jax.lax.optimization_barrier(gathered)
    return _bgpp_formal_attend(q, gathered, idx_valid, cfg)


def _bgpp_paged_decode_attend_sharded(q, store, gi, phys, valid, cfg, layout,
                                      rules):
    """Route the two-phase paged BGPP decode device-local per head shard.

    Left to GSPMD, the progressive plane gathers and ``top_k`` selections
    of phase 1 get partitioned by REPLICATING the head axis — all-gathers
    of the plane pools across ``"model"`` on every round, exactly the
    cross-shard traffic the two-phase split exists to avoid.  With a mesh
    attached this wraps the whole attend in ``shard_map``: each device runs
    phase 1 + top-k + the phase-2 survivor gather on its own head shard of
    the pool (batch likewise over ``"data"``), introducing no collective at
    all — the head outputs rejoin at the decode layer's attend-reduction
    all-gather like every other format.  tests/test_multidevice.py pins
    this structurally (no collective in the compiled body).

    Falls back to the plain call when there is no mesh, the model axis is
    trivial, or the head counts don't divide it (the same divisibility
    fallback the cache placement applies — the pool is then replicated and
    there is nothing to keep local).
    """
    mesh = getattr(rules, "mesh", None)
    run = lambda q_, store_, phys_, valid_: _bgpp_paged_decode_attend(
        q_, store_, gi, phys_, valid_, cfg
    )
    if mesh is None:
        return run(q, store, phys, valid)
    m = dict(mesh.shape).get(rules.model_axis, 1)
    if m <= 1 or cfg.num_kv_heads % m or cfg.num_heads % m:
        return run(q, store, phys, valid)
    spec = lambda axes, x: rules.spec_for_shape(mesh, axes, x.shape)
    store_spec = jax.tree.map(
        lambda axes, x: spec(tuple(axes), x),
        kvc.cache_specs(cfg, layout)["global"], store,
        is_leaf=lambda x: isinstance(x, tuple),
    )
    return jax.shard_map(
        run, mesh=mesh,
        in_specs=(
            spec((sh.BATCH, sh.HEADS, None), q),
            store_spec,
            spec((sh.BATCH, None), phys),
            spec((sh.BATCH, None), valid),
        ),
        out_specs=spec((sh.BATCH, sh.HEADS, None), q),
        check_vma=False,
    )(q, store, phys, valid)


# --------------------------------------------------------------------------
# per-layer decode bodies
# --------------------------------------------------------------------------


def _paged_kw(layout):
    return dict(page_size=layout.page_size, max_seq=layout.max_seq)


def _attn_decode_layer(p, cfg, layout, cache, x, pos, layer_idx, theta, rules,
                       phys=None, decode_mode="jnp"):
    """x: (B, 1, D), pos: per-slot (B,) int32.  Returns (out (B,1,D), cache).

    Every batch row carries its own position: RoPE angles, the KV write
    target, and the causal/window valid mask are all computed per slot, so
    requests admitted at different times decode together in one batch.

    ``phys`` (paged layouts): the precomputed ``(B, S_max)`` logical->pool
    gather map — global writes translate through the page table and the
    attend consumes the gathered heads-major view, which holds exactly the
    slot layout's values (bit-identical decode).

    ``decode_mode`` (resolved once at :func:`make_serve_step` build time by
    :mod:`repro.serving.kernel_decode`): ``"jnp"`` keeps the legacy engine
    attends; ``"interpret"``/``"compiled"`` route the GLOBAL-layer decode
    attend through the Pallas paged-attention kernel families (local ring
    windows and cross-attention stay jnp — their ring/memory layouts are
    not paged).  The kernel call may decline (mesh the heads don't divide),
    in which case the jnp path below runs unchanged.
    """
    B = x.shape[0]
    fmt = layout.kv_format
    h = layers.apply_norm(x, p["attn_norm"], cfg.norm) if "attn_norm" in p else x
    positions = pos[:, None].astype(jnp.int32)  # (B, 1)
    use_rope = cfg.family != "hybrid"
    q, k, v = layers.qkv_project(
        p["attn"], h, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
        positions if use_rope else None, theta, qk_norm=cfg.qk_norm,
    )
    # heads-parallel decode: q/k/v shard over "model" so the cache write
    # and the whole attend stay device-local per head shard (no-op off-mesh)
    q = sh.constrain(q, rules, (sh.BATCH, None, sh.HEADS, None))
    k = sh.constrain(k, rules, (sh.BATCH, None, sh.KV_HEADS, None))
    v = sh.constrain(v, rules, (sh.BATCH, None, sh.KV_HEADS, None))
    kind, w = cfg.layer_attn_window(layer_idx)
    is_local = layer_idx in layout.local_layers
    pos_c = pos[:, None]  # (B, 1) for masks against (B, S) position grids

    if is_local:
        li = layout.local_layers.index(layer_idx)
        slot = jnp.mod(pos, layout.local_window)  # (B,) per-slot ring index
        store = kvc.write_token(cache["local"], li, k, v, slot)
        store["abs_pos"] = store["abs_pos"].at[li, jnp.arange(B), slot].set(pos)
        cache["local"] = store
        abs_pos = store["abs_pos"][li]  # (B, W)
        if kind == "chunked":
            valid = (abs_pos >= 0) & (abs_pos // w == pos_c // w) & (abs_pos <= pos_c)
        else:
            valid = (abs_pos >= 0) & (pos_c - abs_pos < w) & (abs_pos <= pos_c)
        fmt_l = "int8" if "k_scale" in store else "bf16"
        entry = kvc.slot_entry(store, li, heads=cfg.num_kv_heads)
        out = _decode_attend(q[:, 0], entry, valid, cfg, fmt_l)
    else:
        gi = layout.global_layers.index(layer_idx)
        valid = jnp.arange(layout.max_seq)[None, :] <= pos_c  # (B, S)
        if layout.layout == "paged":
            cache["global"] = kvc.write_token(
                cache["global"], gi, k, v, pos,
                page_table=cache["page_table"], **_paged_kw(layout),
            )
            out = None
            if decode_mode != "jnp":
                out = kernel_decode.decode_attend(
                    q[:, 0], cache["global"], gi, pos, cfg, layout, rules,
                    decode_mode, page_table=cache["page_table"],
                )
            if out is None and fmt == "bgpp":
                # two-phase attend: bit-planes first, then only the top-k
                # survivors' full rows — never the whole paged row; on a
                # mesh the whole thing runs shard_map'd per head shard
                out = _bgpp_paged_decode_attend_sharded(
                    q[:, 0], cache["global"], gi, phys, valid, cfg,
                    layout, rules,
                )
            elif out is None:
                entry = kvc.paged_entry(cache["global"], gi, phys,
                                        heads=cfg.num_kv_heads)
                # pin the gathered view as a materialization point: without
                # it XLA fuses the page gather INTO the attend, and the
                # fused lowering's float reduction order shifts once any
                # program input is sharded — the barrier keeps sharded and
                # single-device decode bit-identical (sharding-parity fuzz)
                entry = jax.lax.optimization_barrier(entry)
                out = _decode_attend(q[:, 0], entry, valid, cfg, fmt)
        else:
            cache["global"] = kvc.write_token(cache["global"], gi, k, v, pos)
            out = None
            if decode_mode != "jnp":
                out = kernel_decode.decode_attend(
                    q[:, 0], cache["global"], gi, pos, cfg, layout, rules,
                    decode_mode,
                )
            if out is None:
                entry = kvc.slot_entry(cache["global"], gi,
                                       heads=cfg.num_kv_heads)
                if fmt == "bgpp":
                    out = _bgpp_decode_attend(q[:, 0], entry, valid, cfg)
                else:
                    out = _decode_attend(q[:, 0], entry, valid, cfg, fmt)

    # the head outputs stay on their devices into the row-parallel wo
    out = _row_parallel(out.reshape(B, 1, -1), p["attn"]["wo"], rules)
    if cfg.post_norms and "post_attn_norm" in p:
        out = layers.apply_norm(out, p["post_attn_norm"], cfg.norm)
    return out, cache


def _ffn_decode_layer(p, cfg, x, rules=None):
    h = layers.apply_norm(x, p["mlp_norm"] if "mlp_norm" in p else p["norm2"], cfg.norm)
    if "moe" in p:
        # dropless routing at decode: GShard capacity is pooled across the
        # batch dim, so capacity drops would couple co-scheduled slots — a
        # slot's logits must never depend on its batch neighbors (the
        # continuous-batching isolation invariant).  capacity_factor=E
        # clamps capacity to Tg*k exactly, and at S=1 the buffer is tiny.
        out, _ = moe.moe_apply(
            p["moe"], h, cfg, capacity_factor=float(cfg.num_experts),
            rules=rules,
        )
    else:
        # gate/up column-parallel over FF, down row-parallel
        out = layers.mlp_apply(
            p["mlp"], h, cfg.activation,
            down=functools.partial(_row_parallel, rules=rules, axis=sh.FF,
                                   scope="tp.mlp_out"),
        )
    if cfg.post_norms and "post_mlp_norm" in p:
        out = layers.apply_norm(out, p["post_mlp_norm"], cfg.norm)
    return out


def _mamba_decode_layer(p, cfg, layout, cache, x, layer_idx, rules=None):
    mi = layout.mamba_layers.index(layer_idx)
    h = layers.apply_norm(x, p["norm1"], cfg.norm)
    state = {
        "h": cache["mamba"]["h"][mi],
        "conv": cache["mamba"]["conv"][mi],
    }
    out, new_state = mamba2.mixer_decode_step(p["mamba"], cfg, h, state, rules)
    h_new = new_state["h"]
    if rules is not None:
        # pin the (B, heads, P, N) state update: the outer-product einsum
        # otherwise drops the head (model) sharding and every one of
        # jamba's 63 mamba layers materializes an unsharded ~1 GB temp
        h_new = sh.constrain(h_new, rules, (sh.BATCH, sh.FF, None, None))
    cache["mamba"]["h"] = cache["mamba"]["h"].at[mi].set(h_new)
    cache["mamba"]["conv"] = cache["mamba"]["conv"].at[mi].set(
        new_state["conv"].astype(cache["mamba"]["conv"].dtype)
    )
    return out, cache


def _sinusoid_at(pos, dim: int) -> jax.Array:
    """Per-slot sinusoidal embedding: (B,) positions -> (B, dim) (avoids a
    (max_seq, D) constant)."""
    div = jnp.exp(
        jnp.arange(0, dim, 2, dtype=jnp.float32) * (-math.log(10000.0) / dim)
    )
    ang = pos.astype(jnp.float32)[:, None] * div  # (B, dim/2)
    out = jnp.zeros(pos.shape + (dim,), jnp.float32)
    out = out.at[..., 0::2].set(jnp.sin(ang))
    return out.at[..., 1::2].set(jnp.cos(ang))


# --------------------------------------------------------------------------
# serve_step builders
# --------------------------------------------------------------------------


def make_serve_step(cfg, layout: kvc.CacheLayout, rules=sh.ShardingRules()):
    """Build the pure batched decode step for one (cfg, layout, rules):

        serve_step(params, cache, tokens (B, 1)) -> (logits (B, 1, V), cache')

    One call decodes ONE token for every batch slot at its own
    ``cache["pos"]``; the scheduler jits it once and drives it for every
    live mix of staggered requests.  Paged layouts hoist one
    logical->pool gather map (:func:`repro.serving.kv_cache.phys_table`)
    per step; ``kv_format="bgpp"`` global layers then attend two-phase —
    bit-plane prediction first, full-precision gather only for the
    surviving top-k (:func:`_bgpp_paged_decode_attend`).

    Rollback contract (speculative decoding relies on this): the step is
    write-then-attend with per-slot validity masks (``arange <= pos``) and
    out-of-range scatter indices dropping, so a position's contents are
    only ever observed in a step that has ALREADY rewritten them from the
    fed token.  Rewinding ``cache["pos"]`` after speculative steps is
    therefore sufficient to un-happen them on slot layouts — global
    layers only; sliding-window rings physically overwrite window lanes,
    which is why ``spec_decode`` refuses local-layer stacks — and paged
    layouts additionally rewind the page allocator so freed pages can't
    service a later prefix hit (``PageAllocator.rewind_slot``).
    """
    dtype = layers._dtype(cfg.dtype)
    thetas = transformer.layer_thetas(cfg) if cfg.family != "ssm" else None
    cspecs = kvc.cache_specs(cfg, layout)
    # decode_kernel knob, resolved ONCE per built step (env > config >
    # backend): "jnp" keeps every legacy path bit-for-bit; kernel modes
    # route global-layer decode attends through repro.kernels families
    decode_mode = kernel_decode.resolve(cfg)
    if decode_mode != "jnp" and layout.global_layers:
        kernel_decode.validate(cfg, layout)
    # weight_format knob, resolved ONCE per built step exactly like
    # decode_kernel (env > config): "bf16" leaves every contraction
    # byte-for-byte the raw-leaf path; int8/bstc require the quantized
    # records weights.prepare_serve_params builds (the scheduler feeds
    # them) and layers.wdot dequantizes at trace time
    weight_format = swt.resolve(cfg)
    if weight_format != "bf16":
        swt.validate(cfg)

    def serve_step(params, cache, tokens):
        """One batched decode token for every slot at its own position."""
        if weight_format != "bf16":
            swt.check_serve_params(params, cfg, weight_format)
        pos = cache["pos"]  # per-slot (B,) int32 positions
        B = tokens.shape[0]
        # paged: one logical->pool gather map serves every global layer
        phys = kvc.phys_table(
            cache["page_table"], layout.page_size, layout.max_seq
        ) if layout.layout == "paged" and layout.global_layers else None
        if phys is not None:
            # the table is replicated; batch-shard the derived gather map so
            # paged reads split over "data" like slot stacks do
            phys = sh.constrain(phys, rules, (sh.BATCH, None))
        x = params["embed"][tokens[:, :1]].astype(dtype)
        if cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype)
        x = sh.constrain(x, rules, (sh.BATCH, None, None))

        if cfg.family in ("dense", "moe", "vlm"):
            for i in range(cfg.num_layers):
                p = jax.tree.map(lambda a: a[i], params["layers"])
                a, cache = _attn_decode_layer(
                    p, cfg, layout, cache, x, pos, i, float(thetas[i]), rules,
                    phys=phys, decode_mode=decode_mode,
                )
                x = x + a
                x = x + _ffn_decode_layer(p, cfg, x, rules)
        elif cfg.family == "ssm":
            for i in range(cfg.num_layers):
                p = jax.tree.map(lambda a: a[i], params["layers"])
                m, cache = _mamba_decode_layer(
                    {"norm1": p["norm"], "mamba": p["mixer"]}, cfg, layout,
                    cache, x, i, rules,
                )
                x = x + m
        elif cfg.family == "hybrid":
            period = cfg.attn_every
            for i in range(cfg.num_layers):
                b, j = divmod(i, period)
                p = jax.tree.map(lambda a: a[b], params["blocks"][f"pos{j}"])
                if cfg.layer_is_attention(i):
                    pa = {"attn_norm": p["norm1"], "attn": p["attn"]}
                    a, cache = _attn_decode_layer(
                        pa, cfg, layout, cache, x, pos, i, cfg.rope_theta,
                        rules, phys=phys, decode_mode=decode_mode,
                    )
                    x = x + a
                else:
                    m, cache = _mamba_decode_layer(p, cfg, layout, cache, x, i, rules)
                    x = x + m
                x = x + _ffn_decode_layer(p, cfg, x, rules)
        elif cfg.family == "enc_dec":
            x = x + _sinusoid_at(pos, cfg.d_model).astype(dtype)[:, None, :]
            for i in range(cfg.num_layers):
                p = jax.tree.map(lambda a: a[i], params["decoder"])
                pa = {"attn_norm": p["norm1"], "attn": p["attn"]}
                a, cache = _attn_decode_layer(
                    pa, cfg, layout, cache, x, pos, i, cfg.rope_theta, rules,
                    phys=phys, decode_mode=decode_mode,
                )
                x = x + a
                # cross attention over the (precomputed) encoder memory
                h = layers.apply_norm(x, p["norm_x"], cfg.norm)
                q = (h @ p["xattn"]["wq"]).reshape(
                    B, cfg.num_heads, cfg.head_dim
                )
                out = _decode_attend(
                    q,
                    {"k": cache["cross_k"][i], "v": cache["cross_v"][i]},
                    jnp.ones((B, cfg.encoder_seq), bool),
                    cfg,
                    "bf16",
                )
                x = x + out.reshape(B, 1, -1) @ p["xattn"]["wo"]
                h = layers.apply_norm(x, p["norm2"], cfg.norm)
                x = x + layers.mlp_apply(p["mlp"], h, "gelu")
        else:
            raise ValueError(cfg.family)

        x = layers.apply_norm(x, params["final_norm"], cfg.norm)
        head = params.get("lm_head")
        if head is None:  # tied: non-bf16 serve params carry an explicit record
            head = params["embed"].T.astype(dtype)
        logits = layers.wdot(x, head)
        logits = sh.constrain(logits, rules, (sh.BATCH, None, sh.VOCAB))
        cache["pos"] = pos + 1
        # pin output placements so donated cache buffers are reused in
        # place across steps instead of drifting to whatever the
        # partitioner last inferred (no-op without a mesh)
        cache = kvc.constrain_cache(cache, cspecs, rules)
        return logits, cache

    return serve_step


def jit_serve_step(cfg, layout: kvc.CacheLayout, rules=sh.ShardingRules()):
    """The jitted :func:`make_serve_step` the scheduler drives, with the
    cache donated: the step's KV writes update it in place, so one cache is
    resident and none is copied into a fresh output.  Callers rebind their
    cache to the step's result and never touch the one they passed."""
    return jax.jit(make_serve_step(cfg, layout, rules), donate_argnums=(1,))


# --------------------------------------------------------------------------
# prefill (build the cache from a prompt) — transformer families
# --------------------------------------------------------------------------


def prefill(params, cfg, layout: kvc.CacheLayout, tokens, rules=sh.ShardingRules(),
            **fw_kw):
    """Runs the forward pass, returning (last_logits, populated cache).

    Transformer families only (mamba/hybrid prefill state capture is in the
    per-family paths of the examples); decode cells of the dry-run take the
    cache as an *input spec*, so this is the serving-path utility.
    """
    assert cfg.family in ("dense", "moe", "vlm")
    logits, _, kvs = transformer.forward(
        params, cfg, tokens, rules, return_kv=True, **fw_kw
    )
    k_all, v_all = kvs  # (L, B, S, Hk, Dh)
    cache, _ = kvc.init_cache(cfg, layout)
    B, S = tokens.shape

    paged_kw = {}
    if layout.layout == "paged":
        # whole-batch prefill maps every slot's row slot-major (no
        # allocator in the loop); the scheduler path syncs its own table
        cache["page_table"] = kvc.identity_page_table(layout)
        paged_kw = dict(page_table=cache["page_table"], **_paged_kw(layout))
    for gi, layer in enumerate(layout.global_layers):
        cache["global"] = kvc.write_prefill(
            cache["global"], gi, k_all[layer], v_all[layer], **paged_kw
        )
    for li, layer in enumerate(layout.local_layers):
        cache["local"] = kvc.write_prefill_local(
            cache["local"], li, k_all[layer], v_all[layer], layout.local_window
        )

    cache["pos"] = jnp.full((B,), S, jnp.int32)
    return logits[:, -1:], cache


def prefill_into_slot(params, cfg, layout: kvc.CacheLayout, cache, slot: int,
                      prompt, rules=sh.ShardingRules(), **fw_kw):
    """Prefill ONE prompt into batch row ``slot`` of a *live* cache.

    This is the admission path of the continuous-batching scheduler: the
    forward pass runs at B=1, the slot is reset (stale KV, ring positions,
    mamba state), and the prompt's quantized/bit-planed KV is written into
    that single batch index without touching live neighbors.  Returns
    ``(last_logits (1, 1, V), cache)`` — the logits sample the request's
    first token.

    prompt: (S,) or (1, S) int32 tokens, S < layout.max_seq (a prompt that
    fills the cache leaves no index for the first decoded token's KV —
    out-of-bounds scatters drop silently, corrupting logits).

    Paged layouts: the caller must have mapped pages covering ``[0, S)``
    of the slot's row in ``cache["page_table"]`` first (the scheduler's
    ``PageAllocator.ensure_range``) — writes through unmapped pages drop.

    This is the *eager reference* admission path: one arbitrary-length
    forward per prompt, recompiling per length and copying the stacked
    store per layer.  Production admission is :class:`ChunkedPrefill` —
    fixed-shape ``(1, C)`` chunks, jitted once per bucket width with the
    cache donated — which the scheduler interleaves with batched decode.
    """
    assert cfg.family in ("dense", "moe", "vlm")
    tokens = prompt[None] if prompt.ndim == 1 else prompt
    assert tokens.shape[0] == 1, "one prompt per admission"
    S = tokens.shape[1]
    assert S < layout.max_seq, (
        f"prompt len {S} needs at least one decode slot below max_seq "
        f"{layout.max_seq}"
    )
    logits, _, (k_all, v_all) = transformer.forward(
        params, cfg, tokens, rules, return_kv=True, **fw_kw
    )
    cache = kvc.reset_slot(cache, layout, slot)
    paged_kw = dict(
        page_table=cache["page_table"], **_paged_kw(layout)
    ) if layout.layout == "paged" else {}
    for gi, layer in enumerate(layout.global_layers):
        cache["global"] = kvc.write_prefill(
            cache["global"], gi, k_all[layer], v_all[layer], slot=slot,
            **paged_kw
        )
    for li, layer in enumerate(layout.local_layers):
        cache["local"] = kvc.write_prefill_local(
            cache["local"], li, k_all[layer], v_all[layer],
            layout.local_window, slot=slot,
        )
    cache["pos"] = cache["pos"].at[slot].set(S)
    return logits[:, -1:], cache


# --------------------------------------------------------------------------
# chunked, bucketed prefill — the jitted admission path
# --------------------------------------------------------------------------
#
# A chunk step runs a fixed-shape (1, C) forward for one slot of a live
# cache at an arbitrary token offset.  Two ingredients make the composition
# of chunks BIT-IDENTICAL (bf16) to a single whole-prompt chunk:
#
#   * global layers write the chunk's KV into the cache FIRST and then
#     attend over the full (S_max,) stack row with per-query causal masks —
#     the key axis has one fixed shape and one fixed value layout no matter
#     how the prompt was chunked, so per-query reductions associate
#     identically;
#   * local (ring) layers attend per query over a gathered fixed-width
#     window (lane r of query p always holds position p - W + 1 + r), so
#     lane placement is chunking-invariant too.  Ring writes happen after
#     the attend (a chunk write would evict window entries its own earlier
#     queries still need).
#
# Padded lanes beyond ``length`` carry garbage queries (their logits are
# never read) and their KV writes scatter to kvc.OOB_INDEX (dropped).


def _chunk_attend_local(cfg, layout, store, li, slot, q, k, v, qpos, offset,
                        kind, w):
    """Fixed-width gathered-window attention for a ring-buffered local layer.

    q/k/v: fresh chunk projections ``(1, C, H, Dh)``; qpos ``(C,)`` global
    positions; the ring row holds positions ``< offset``.  Query at position
    p attends lanes holding positions ``p-W+1 .. p`` gathered from
    [ring (position-ordered) | fresh chunk], masked by presence + the
    sliding/chunked window rule.  Returns f32 ``(1, C, Hq, Dh)``.
    """
    B, C, Hq, Dh = q.shape
    Hk = cfg.num_kv_heads
    g = Hq // Hk
    W = layout.local_window

    ring = {n: a[0] for n, a in
            kvc.slot_entry(store, li, slot, heads=Hk).items()}
    if "k_scale" in ring:  # heads-major (Hk, W, ...)
        kr = ring["k"].astype(jnp.float32) * ring["k_scale"][..., None]
        vr = ring["v"].astype(jnp.float32) * ring["v_scale"][..., None]
    else:
        kr = ring["k"].astype(jnp.float32)
        vr = ring["v"].astype(jnp.float32)
    ap = store["abs_pos"][li, slot]  # (W,)

    # reorder ring lanes to ascending position: lane j holds offset-W+j
    order = jnp.mod(offset - W + jnp.arange(W), W)
    kr, vr, ap = kr[:, order], vr[:, order], ap[order]
    ring_pos = jnp.where((ap >= 0) & (ap < offset), ap, -(1 << 30))

    buf_k = jnp.concatenate([kr, jnp.swapaxes(k[0], 0, 1).astype(jnp.float32)],
                            axis=1)  # (Hk, W+C, D)
    buf_v = jnp.concatenate([vr, jnp.swapaxes(v[0], 0, 1).astype(jnp.float32)],
                            axis=1)
    buf_pos = jnp.concatenate([ring_pos, qpos])  # (W+C,)

    # query i gathers buffer lanes i+1 .. i+W == positions qpos[i]-W+1..qpos[i]
    idx = jnp.arange(C)[:, None] + 1 + jnp.arange(W)[None, :]  # (C, W)
    gk = buf_k[:, idx]  # (Hk, C, W, D)
    gv = buf_v[:, idx]
    expect = qpos[:, None] - W + 1 + jnp.arange(W)[None, :]  # (C, W)
    valid = (buf_pos[idx] == expect) & (expect >= 0)
    if kind == "chunked":
        cw = jnp.maximum(w, 1)
        valid = valid & (expect // cw == qpos[:, None] // cw)
    else:  # sliding; no-op when w == W, real when W was clamped to max_seq
        valid = valid & (qpos[:, None] - expect < w)

    qg = q[0].reshape(C, Hk, g, Dh).transpose(1, 2, 0, 3).astype(jnp.float32)
    logits = jnp.einsum("hgqd,hqwd->hgqw", qg, gk) * Dh**-0.5
    logits = jnp.where(valid[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("hgqw,hqwd->hgqd", probs, gv)
    return out.transpose(2, 0, 1, 3).reshape(1, C, Hq, Dh)


def _attn_chunk_layer(p, cfg, layout, cache, x, slot, offset, length,
                      layer_idx, theta, rules, phys=None):
    """One attention layer of the chunk forward.  x: (1, C, D).
    ``phys`` (paged): the slot's ``(1, S_max)`` logical->pool gather map,
    hoisted once per chunk step."""
    B, C, _ = x.shape
    fmt = layout.kv_format
    h = layers.apply_norm(x, p["attn_norm"], cfg.norm) if "attn_norm" in p else x
    qpos = offset + jnp.arange(C, dtype=jnp.int32)  # (C,) global positions
    q, k, v = layers.qkv_project(
        p["attn"], h, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
        qpos[None], theta, qk_norm=cfg.qk_norm,
    )
    # B=1 keeps "data" replicated here; heads still shard over "model"
    q = sh.constrain(q, rules, (sh.BATCH, None, sh.HEADS, None))
    k = sh.constrain(k, rules, (sh.BATCH, None, sh.KV_HEADS, None))
    v = sh.constrain(v, rules, (sh.BATCH, None, sh.KV_HEADS, None))
    kind, w = cfg.layer_attn_window(layer_idx)

    if layer_idx in layout.local_layers:
        li = layout.local_layers.index(layer_idx)
        out = _chunk_attend_local(
            cfg, layout, cache["local"], li, slot, q, k, v, qpos, offset,
            kind, w,
        )
        cache["local"] = kvc.write_prefill_local(
            cache["local"], li, k, v, layout.local_window,
            slot=slot, offset=offset, length=length,
        )
    else:
        gi = layout.global_layers.index(layer_idx)
        # write first: chunk keys are read back from the stack, keeping the
        # key axis (S_max,) for every bucket width
        if layout.layout == "paged":
            cache["global"] = kvc.write_prefill(
                cache["global"], gi, k, v, slot=slot, offset=offset,
                length=length, page_table=cache["page_table"],
                **_paged_kw(layout),
            )
            view = kvc.paged_entry(cache["global"], gi, phys,
                                   heads=cfg.num_kv_heads)
            # same materialization pin as the decode layer: stop the page
            # gather fusing into the chunk attend (sharding-stable lowering)
            view = jax.lax.optimization_barrier(view)
        else:
            cache["global"] = kvc.write_prefill(
                cache["global"], gi, k, v, slot=slot, offset=offset,
                length=length,
            )
            view = kvc.slot_entry(cache["global"], gi, slot,
                                  heads=cfg.num_kv_heads)
        S = layout.max_seq
        valid = (jnp.arange(S)[None, :] <= qpos[:, None])[None]  # (1, C, S)
        if fmt == "bgpp":
            # prefill attends the full causal context: reconstruct the exact
            # int8 K from the bit planes (BGPP's progressive prediction is a
            # decode-time saving; there is nothing to skip at prefill)
            entry = {
                "k": kvc.bitplanes_to_k(
                    view["k_planes"], view["k_sign"]
                ).astype(jnp.int8),
                "k_scale": view["k_scale"],
                "v": view["v"],
                "v_scale": view["v_scale"],
            }
            out = _cache_attend(q, entry, valid, cfg, "int8")
        else:
            out = _cache_attend(q, view, valid, cfg, fmt)

    out = _row_parallel(out.astype(x.dtype).reshape(B, C, -1),
                        p["attn"]["wo"], rules)
    if cfg.post_norms and "post_attn_norm" in p:
        out = layers.apply_norm(out, p["post_attn_norm"], cfg.norm)
    return out, cache


def make_prefill_chunk(cfg, layout: kvc.CacheLayout, rules=sh.ShardingRules()):
    """Builds the pure chunk step for one (cfg, layout):

        prefill_chunk(params, cache, tokens (1, C), slot, offset, length)
            -> (logits (1, C, V), cache')

    ``slot``/``offset``/``length`` are traced int32 scalars, so one jit
    compilation per chunk width ``C`` covers every slot, token offset, and
    padding amount.  The chunk's KV lands at positions
    ``[offset, offset+length)`` of row ``slot`` and ``cache['pos'][slot]``
    is set to ``offset + length`` (absolute, so interleaved decode steps of
    other slots can never drift a prefilling row's position).
    """
    assert cfg.family in ("dense", "moe", "vlm"), (
        "chunked admission covers transformer families; ssm/hybrid/enc-dec"
        " decode through make_serve_step directly"
    )
    dtype = layers._dtype(cfg.dtype)
    thetas = transformer.layer_thetas(cfg)
    cspecs = kvc.cache_specs(cfg, layout)

    def prefill_chunk(params, cache, tokens, slot, offset, length):
        """One fixed-shape (1, C) prefill chunk against the live cache."""
        # paged: this slot's logical->pool gather row, hoisted once for
        # every global layer (the serve_step pattern)
        phys = jnp.take(
            kvc.phys_table(
                cache["page_table"], layout.page_size, layout.max_seq
            ),
            slot, axis=0,
        )[None] if layout.layout == "paged" and layout.global_layers else None
        x = params["embed"][tokens].astype(dtype)
        if cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype)
        x = sh.constrain(x, rules, (sh.BATCH, None, None))
        for i in range(cfg.num_layers):
            p = jax.tree.map(lambda a: a[i], params["layers"])
            a, cache = _attn_chunk_layer(
                p, cfg, layout, cache, x, slot, offset, length, i,
                float(thetas[i]), rules, phys=phys,
            )
            x = x + a
            # dropless MoE (capacity_factor=E): padded garbage lanes can
            # never steal expert capacity from valid prompt tokens
            x = x + _ffn_decode_layer(p, cfg, x, rules)
        x = layers.apply_norm(x, params["final_norm"], cfg.norm)
        head = params.get("lm_head")
        logits = x @ (head if head is not None else params["embed"].T.astype(dtype))
        logits = sh.constrain(logits, rules, (sh.BATCH, None, sh.VOCAB))
        cache["pos"] = cache["pos"].at[slot].set(offset + length)
        cache = kvc.constrain_cache(cache, cspecs, rules)
        return logits, cache

    return prefill_chunk


def default_buckets(chunk_budget: int) -> Tuple[int, ...]:
    """Bucket widths for a token budget: the budget itself plus one half-
    size tail bucket (fewer wasted pad lanes on the last chunk of a prompt,
    at the cost of one extra compile)."""
    budget = max(1, int(chunk_budget))
    return tuple(sorted({budget, max(4, budget // 2)} - {0}))


class ChunkedPrefill:
    """Jitted, bucketed chunk-prefill engine for one (cfg, layout, rules).

    Owns two donated-cache jits: the chunk step (compiled once per bucket
    width — assert via :attr:`num_compiles`) and the slot reset.  The
    scheduler drives it chunk-by-chunk; :meth:`admit` runs a whole prompt
    (used by tests/benchmarks as the whole-prompt reference: with a bucket
    >= the prompt length it is a single fixed-shape forward).
    """

    def __init__(self, cfg, layout: kvc.CacheLayout,
                 rules: sh.ShardingRules = sh.ShardingRules(),
                 buckets: Tuple[int, ...] = (8, 16)):
        self.cfg = cfg
        self.layout = layout
        self.buckets = tuple(sorted({int(b) for b in buckets}))
        assert self.buckets and self.buckets[0] >= 1
        self._chunk = jax.jit(
            make_prefill_chunk(cfg, layout, rules), donate_argnums=(1,)
        )
        self._reset = jax.jit(
            lambda cache, slot: kvc.reset_slot(cache, layout, slot),
            donate_argnums=(0,),
        )

    def bucket_for(self, n: int) -> int:
        """Smallest bucket >= n, or the largest bucket (caller chunks)."""
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    @property
    def num_compiles(self) -> int:
        """Compiled chunk variants — the donate/bucketing contract says this
        never exceeds ``len(self.buckets)``."""
        return self._chunk._cache_size()

    def reset(self, cache, slot: int):
        """Donated-cache slot scrub (the first step of every admission)."""
        return self._reset(cache, int(slot))

    def run_chunk(self, params, cache, slot: int, chunk_tokens, offset: int):
        """One fixed-shape chunk step: pads ``chunk_tokens`` (1-D, length
        n <= largest bucket) to its bucket and runs the jitted step.
        Returns ``(logits (1, C, V), cache, n)``."""
        toks = np.asarray(chunk_tokens, np.int32).reshape(-1)
        n = toks.shape[0]
        C = self.bucket_for(n)
        assert n <= C, f"chunk of {n} tokens exceeds largest bucket {C}"
        if n < C:
            toks = np.pad(toks, (0, C - n))
        logits, cache = self._chunk(
            params, cache, jnp.asarray(toks[None]), int(slot), int(offset),
            int(n),
        )
        return logits, cache, n

    def admit(self, params, cache, slot: int, prompt, *,
              max_chunk: Optional[int] = None, reset: bool = True):
        """Whole-prompt admission through the chunk path: reset the slot,
        then consume the prompt in <= ``max_chunk``-token chunks (default:
        the largest bucket).  Returns ``(last_logits (1, 1, V), cache)`` —
        same contract as :func:`prefill_into_slot`."""
        toks = np.asarray(prompt, np.int32).reshape(-1)
        S = toks.shape[0]
        assert 0 < S < self.layout.max_seq
        step = min(self.buckets[-1], max_chunk or self.buckets[-1])
        if reset:
            cache = self.reset(cache, slot)
        off = 0
        logits, n = None, 0
        while off < S:
            logits, cache, n = self.run_chunk(
                params, cache, slot, toks[off:off + step], off
            )
            off += n
        return logits[:, n - 1:n], cache
