"""Pallas body for the page-table-aware flash decode kernel.

The kernel reads the whole stacked cache in place.  K/V arrive as
``(L, n_tok, Hk*D)`` token rows: a paged layout's pools as stored, or a
slot layout's ``(L, B, S, Hk*D)`` stack with its leading dims merged,
which is free.  The layer to attend is a scalar-prefetched index, so no
step program copies one layer out of the stack for the custom call.  Grid
``(B, pages_per_slot)``: each slot walks its page list through the
scalar-prefetched page table, and a K/V block is one page of every local
head, ``(P, Hk*D)`` at ``(layer, page_ids[b, j], 0)`` — one contiguous
DMA in the stack's own tiling.  A head's rows are the lane block
``[h*D, (h+1)*D)`` of a row, whole vregs when ``D`` is a multiple of 128,
so taking them costs no relayout.  (Rows stored ``(Hk, D)`` per token
would tile one token per ``(8, 128)`` tile; splitting those by head takes
a byte shuffle of every int8 tile, several times the kernel's own time.)

Int8 scales come in one of two forms.  Heads-major per-slot rows
``(L, B, Hk, S)``, the slot stacks' own layout, are read in place as one
``(Hk, S)`` block per slot.  A paged layout's token-major pools
``(L, n_tok, Hk)`` are gathered through the page table into those rows
for the one layer, outside the kernel (:func:`slot_scale_rows`); they are
``1/D`` of the pool's bytes.

Numerics are DEFERRED-softmax, not online-softmax: page steps only stage
K and V into VMEM scratch; the last page step runs, per head, one
``(g, S)`` score dot, masks by position, one exact softmax and one
``(g, S) @ (S, D)`` PV dot — the operation order of the jnp oracle
(``ref.py``), which interpret mode reproduces bit-for-bit.  Against the
serving engine's own jnp attend and in compiled mode the agreement is to
float rounding (the dots may accumulate in a different order), not
bitwise.

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


# bytes of one K (or V) page block that a slot layout's sub-page aims at:
# large against a grid step's fixed cost, small enough that the double-
# buffered blocks and the staged slot fit VMEM at a long context
_BLOCK_BYTES = 1 << 20
# VMEM beyond the buffers counted in _vmem_bytes: Mosaic's own scratch and
# tile padding
_VMEM_SLACK = 8 << 20


def slot_page_rows(S: int, width: int, itemsize: int) -> int:
    """Rows ``P`` of the sub-pages a slot stack of ``width``-element rows
    is read in: the largest divisor of ``S`` that is a multiple of the
    dtype's sublane tile and keeps a ``(P, width)`` block within
    ``_BLOCK_BYTES``; ``S`` itself (one page per slot) where no divisor
    is."""
    tile = 32 // itemsize  # 8 rows f32, 16 bf16, 32 int8
    most = min(S, _BLOCK_BYTES // (width * itemsize))
    for P in range(most - most % tile, 0, -tile):
        if S % P == 0:
            return P
    return S


def slot_table(B: int, S: int, page_size: int) -> jax.Array:
    """Identity page table of a slot stack viewed as ``(B*S, ...)`` rows:
    slot ``b``'s ``j``-th sub-page is page ``b*(S/P) + j``."""
    pp = S // page_size
    return (jnp.arange(B, dtype=jnp.int32)[:, None] * pp
            + jnp.arange(pp, dtype=jnp.int32)[None])


def _vmem_bytes(P: int, S: int, Hk: int, g: int, D: int, itemsize: int,
                scaled: bool) -> int:
    """VMEM the kernel's buffers take: double-buffered K/V page blocks,
    query, output and scale blocks, the staged slot, and one head's f32
    operands of the final dots."""
    rows = -(-g // 8) * 8  # a (g, D) f32 block pads to whole sublane tiles
    need = 2 * 2 * P * Hk * D * itemsize + 2 * 2 * Hk * rows * D * 4
    need += 2 * S * Hk * D * itemsize + 2 * S * D * 4
    if scaled:
        need += 2 * 2 * Hk * S * 4
    return need


def _stage_page(j, page_size, src_ref, buf):
    """Copy this grid step's page block ``(P, Hk*D)`` into rows
    ``[j*P, (j+1)*P)`` of the slot's scratch ``(S, Hk*D)``."""
    rows = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
    buf[rows, :] = src_ref[...]


def _head(buf, h: int, D: int):
    """Head ``h``'s ``(S, D)`` rows of a staged ``(S, Hk*D)`` slot."""
    return buf[:, h * D:(h + 1) * D]


def _bf16_body(li_ref, pids_ref, pos_ref, q_ref, k_ref, v_ref, out_ref,
               k_buf, v_buf, *, scale: float, page_size: int):
    b, j = pl.program_id(0), pl.program_id(1)
    _stage_page(j, page_size, k_ref, k_buf)
    _stage_page(j, page_size, v_ref, v_buf)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        Hk, _, D = q_ref.shape
        for h in range(Hk):
            qb = q_ref[h].astype(jnp.float32)  # (g, D)
            kf = _head(k_buf, h, D).astype(jnp.float32)  # (S, D)
            scores = _qk_dot(qb, kf, jnp.float32) * scale
            lane = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
            logits = jnp.where(lane <= pos_ref[b], scores, NEG_INF)
            probs = _softmax(logits)
            out_ref[h] = _pv_dot(
                probs, _head(v_buf, h, D).astype(jnp.float32), jnp.float32
            )


def _int8_body(li_ref, pids_ref, pos_ref, q_ref, k_ref, v_ref, ks_ref,
               vs_ref, out_ref, k_buf, v_buf, *, scale: float,
               page_size: int):
    b, j = pl.program_id(0), pl.program_id(1)
    _stage_page(j, page_size, k_ref, k_buf)
    _stage_page(j, page_size, v_ref, v_buf)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        Hk, _, D = q_ref.shape
        for h in range(Hk):
            # engine: per-(b,h,g) row absmax/127, clip +-127
            qb = q_ref[h].astype(jnp.float32)  # (g, D)
            qs = jnp.maximum(
                jnp.max(jnp.abs(qb), axis=-1, keepdims=True), 1e-8
            ) / 127.0
            qq = jnp.clip(jnp.round(qb / qs), -127, 127).astype(jnp.int8)
            scores = _qk_dot(qq, _head(k_buf, h, D), jnp.int32)  # (g, S)
            # rescale in the engine's multiply order: (i32 * q_scale) *
            # k_scale * D^-0.5
            logits = (scores.astype(jnp.float32) * qs * ks_ref[h:h + 1, :]
                      * scale)
            lane = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
            logits = jnp.where(lane <= pos_ref[b], logits, NEG_INF)
            probs = _softmax(logits)
            w = probs * vs_ref[h:h + 1, :]
            w_scale = jnp.maximum(
                jnp.max(w, axis=-1, keepdims=True), 1e-20
            ) / 127.0
            w_q = jnp.clip(jnp.round(w / w_scale), 0, 127).astype(jnp.int8)
            out_ref[h] = _pv_dot(
                w_q, _head(v_buf, h, D), jnp.int32
            ).astype(jnp.float32) * w_scale


def slot_scale_rows(scale: jax.Array, page_ids: jax.Array,
                    page_size: int) -> jax.Array:
    """Per-token scales ``(n_tok, Hk)`` -> heads-major per-slot rows
    ``(B, Hk, S)`` through the page table (unmapped pages clamp to page
    0; those lanes are position-masked).  A small XLA gather outside the
    kernel: the scales are ``1/D`` of the pool's bytes and a lane-dense row
    is what the masked softmax consumes."""
    B, pp = page_ids.shape
    lane = jnp.arange(pp * page_size)
    rows = (jnp.maximum(page_ids, 0)[:, lane // page_size] * page_size
            + (lane % page_size)[None])  # (B, S)
    return jnp.moveaxis(scale[rows], 2, 1)


def paged_flash_decode_pallas(
    q: jax.Array,  # (B, Hk, g, D) f32
    k: jax.Array,  # (L, n_tok, Hk*D)
    v: jax.Array,  # (L, n_tok, Hk*D)
    page_ids: jax.Array,  # (B, pages_per_slot) int32
    pos: jax.Array,  # (B,) int32
    layer: jax.Array,  # () int32: the layer of the stack to attend
    *,
    page_size: int,
    k_scale: Optional[jax.Array] = None,  # (L, n_tok, Hk) or (L, B, Hk, S)
    v_scale: Optional[jax.Array] = None,
    interpret: bool = False,
) -> jax.Array:
    """Launch the paged decode kernel on layer ``layer`` -> f32
    ``(B, Hk, g, D)``."""
    B, Hk, g, D = q.shape
    P = page_size
    pp = page_ids.shape[1]
    S = pp * P
    # unmapped pages (-1) clamp to page 0; garbage lanes die at the pos mask
    pids = jnp.maximum(page_ids, 0).astype(jnp.int32)
    li = jnp.reshape(layer, (1,)).astype(jnp.int32)

    slotmap = lambda b, j, li_, pids_, pos_: (b, 0, 0, 0)
    pagemap = lambda b, j, li_, pids_, pos_: (li_[0], pids_[b, j], 0)
    in_specs = [
        pl.BlockSpec((None, Hk, g, D), slotmap),
        pl.BlockSpec((None, P, Hk * D), pagemap),
        pl.BlockSpec((None, P, Hk * D), pagemap),
    ]
    operands = [q.astype(jnp.float32), k, v]
    body = functools.partial(_bf16_body, scale=D**-0.5, page_size=P)

    if k_scale is not None:
        if k_scale.ndim == 3:  # token-major pools: gather this layer's rows
            rows = lambda s: slot_scale_rows(s[li[0]], page_ids, P)[None]
            scalemap = lambda b, j, li_, pids_, pos_: (0, b, 0, 0)
        else:  # heads-major per-slot rows: read in place
            rows = lambda s: s
            scalemap = lambda b, j, li_, pids_, pos_: (li_[0], b, 0, 0)
        in_specs += [pl.BlockSpec((None, None, Hk, S), scalemap)] * 2
        operands += [rows(k_scale), rows(v_scale)]
        body = functools.partial(_int8_body, scale=D**-0.5, page_size=P)

    itemsize = jnp.dtype(k.dtype).itemsize
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, pp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, Hk, g, D), slotmap),
        scratch_shapes=[pltpu.VMEM((S, Hk * D), k.dtype),
                        pltpu.VMEM((S, Hk * D), v.dtype)],
    )
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hk, g, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(
                P, S, Hk, g, D, itemsize, k_scale is not None
            ) + _VMEM_SLACK,
        ),
        interpret=interpret,
        name="paged_flash_decode",
    )(li, pids, pos.astype(jnp.int32), *operands)
