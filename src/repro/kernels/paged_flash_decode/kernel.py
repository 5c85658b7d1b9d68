"""Pallas body for the page-table-aware flash decode kernel.

Grid ``(B, Hk, pages_per_slot)``: each (slot, kv-head) cell walks its page
list via scalar-prefetched page ids — the K/V BlockSpec index maps read
``page_ids[b, j]`` directly, so the DMA engine gathers physical pages
on the fly and no contiguous ``(B, S, Hk, D)`` slot view ever exists in
HBM.  The token-major pools are viewed as ``(n_tok, Hk*D)`` (a free
reshape), so one block is one page of one head, ``(P, D)``: a Mosaic-legal
tile when ``D`` is a multiple of 128 and ``P`` a multiple of the dtype's
sublane tiling (16 for bf16, 32 for int8).  Per-token int8 scales are
gathered per slot outside the kernel into heads-major ``(1, S)`` rows.

Numerics are DEFERRED-softmax, not online-softmax: page steps only stage
K and V into VMEM scratch; the last page step runs one ``(g, S)`` score
dot, masks by position, one exact softmax and one ``(g, S) @ (S, D)`` PV
dot — the operation order of the jnp oracle (``ref.py``), which interpret
mode reproduces bit-for-bit.  Against the serving engine's own jnp attend
and in compiled mode the agreement is to float rounding (the dots may
accumulate in a different order), not bitwise.

The int8 variant mirrors the engine's A2/A3 sequence: per-(g-row) query
quantization, int8×int8 QK^T with int32 accumulation, f32 rescale by
``q_scale * k_scale * D^-0.5``, then v_scale-folded prob quantization and
an int8 PV dot (int32 accumulation — exact, and equal to the oracle's f32
accumulation while ``S * 127**2 < 2**24``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # matches repro.core.attention.NEG_INF


def _softmax(logits):
    """Exact ``jax.nn.softmax`` expansion (max-shift, exp, normalize)."""
    m = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.exp(logits - m)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def _qk_dot(a, b, prefer):
    """(g, D) x (S, D) -> (g, S), contracting D (the engine einsum's axes)."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=prefer
    )


def _pv_dot(p, v, prefer):
    """(g, S) x (S, D) -> (g, D)."""
    return jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=prefer
    )


def _stage_page(j, page_size, src_ref, buf):
    """Copy this grid step's page block into rows ``[j*P, (j+1)*P)``."""
    rows = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
    buf[rows, :] = src_ref[...]


def _bf16_body(pids_ref, pos_ref, q_ref, k_ref, v_ref, out_ref,
               k_buf, v_buf, *, scale: float, page_size: int):
    b, j = pl.program_id(0), pl.program_id(2)
    _stage_page(j, page_size, k_ref, k_buf)
    _stage_page(j, page_size, v_ref, v_buf)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        qb = q_ref[...].astype(jnp.float32)  # (g, D)
        kf = k_buf[...].astype(jnp.float32)
        scores = _qk_dot(qb, kf, jnp.float32) * scale
        lane = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        logits = jnp.where(lane <= pos_ref[b], scores, NEG_INF)
        probs = _softmax(logits)
        out_ref[...] = _pv_dot(
            probs, v_buf[...].astype(jnp.float32), jnp.float32
        )


def _int8_body(pids_ref, pos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
               out_ref, k_buf, v_buf, *, scale: float, page_size: int):
    b, j = pl.program_id(0), pl.program_id(2)
    _stage_page(j, page_size, k_ref, k_buf)
    _stage_page(j, page_size, v_ref, v_buf)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        # engine: per-(b,h,g) row absmax/127, clip +-127
        qb = q_ref[...].astype(jnp.float32)  # (g, D)
        qs = jnp.maximum(
            jnp.max(jnp.abs(qb), axis=-1, keepdims=True), 1e-8
        ) / 127.0
        qq = jnp.clip(jnp.round(qb / qs), -127, 127).astype(jnp.int8)
        scores = _qk_dot(qq, k_buf[...], jnp.int32)  # (g, S)
        # rescale in the engine's multiply order: (i32 * q_scale) * k_scale
        # * D^-0.5
        logits = scores.astype(jnp.float32) * qs * ks_ref[...] * scale
        lane = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(lane <= pos_ref[b], logits, NEG_INF)
        probs = _softmax(logits)
        w = probs * vs_ref[...]
        w_scale = jnp.maximum(
            jnp.max(w, axis=-1, keepdims=True), 1e-20
        ) / 127.0
        w_q = jnp.clip(jnp.round(w / w_scale), 0, 127).astype(jnp.int8)
        out_ref[...] = _pv_dot(
            w_q, v_buf[...], jnp.int32
        ).astype(jnp.float32) * w_scale


def slot_scale_rows(scale: jax.Array, page_ids: jax.Array,
                    page_size: int) -> jax.Array:
    """Per-token scales ``(n_tok, Hk)`` -> heads-major per-slot rows
    ``(B, Hk, 1, S)`` through the page table (unmapped pages clamp to page
    0; those lanes are position-masked).  A small XLA gather outside the
    kernel: the scales are ``1/D`` of the pool's bytes and a lane-dense row
    is what the masked softmax consumes."""
    B, pp = page_ids.shape
    lane = jnp.arange(pp * page_size)
    rows = (jnp.maximum(page_ids, 0)[:, lane // page_size] * page_size
            + (lane % page_size)[None])  # (B, S)
    return jnp.moveaxis(scale[rows], 2, 1)[:, :, None, :]


def paged_flash_decode_pallas(
    q: jax.Array,  # (B, Hk, g, D) f32
    k: jax.Array,  # (n_tok, Hk, D)
    v: jax.Array,  # (n_tok, Hk, D)
    page_ids: jax.Array,  # (B, pages_per_slot) int32
    pos: jax.Array,  # (B,) int32
    *,
    page_size: int,
    k_scale: Optional[jax.Array] = None,  # (n_tok, Hk) f32
    v_scale: Optional[jax.Array] = None,
    interpret: bool = False,
) -> jax.Array:
    """Launch the paged decode kernel -> f32 ``(B, Hk, g, D)``."""
    B, Hk, g, D = q.shape
    n_tok = k.shape[0]
    P = page_size
    pp = page_ids.shape[1]
    S = pp * P
    scale = D**-0.5
    # free reshape of the token-major pool into (n_tok, Hk*D): block
    # (P, D) at (page, head) is exactly one physical page of one head
    kp = k.reshape(n_tok, Hk * D)
    vp = v.reshape(n_tok, Hk * D)
    # unmapped pages (-1) clamp to page 0; garbage lanes die at the pos mask
    pids = jnp.maximum(page_ids, 0).astype(jnp.int32)

    qmap = lambda b, h, j, pids_, pos_: (b, h, 0, 0)
    pagemap = lambda b, h, j, pids_, pos_: (pids_[b, j], h)
    in_specs = [
        pl.BlockSpec((None, None, g, D), qmap),
        pl.BlockSpec((P, D), pagemap),
        pl.BlockSpec((P, D), pagemap),
    ]
    operands = [q.astype(jnp.float32), kp, vp]
    body = functools.partial(_bf16_body, scale=scale, page_size=P)

    if k_scale is not None:
        in_specs += [pl.BlockSpec((None, None, 1, S), qmap)] * 2
        operands += [slot_scale_rows(k_scale, page_ids, P),
                     slot_scale_rows(v_scale, page_ids, P)]
        body = functools.partial(_int8_body, scale=scale, page_size=P)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hk, pp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, None, g, D), qmap),
        scratch_shapes=[pltpu.VMEM((S, D), k.dtype), pltpu.VMEM((S, D), v.dtype)],
    )
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hk, g, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_flash_decode",
    )(pids, pos.astype(jnp.int32), *operands)
