"""Tiled online-softmax (flash) attention Pallas kernel.

Needed by the 32k/500k shapes: XLA cannot fuse the S×S logits away on its
own, and the MCBP serving engine needs the sliding/chunked mask families of
the assigned archs (gemma3 local layers, mixtral SWA, llama4 chunked).

Grid: (B·Hq, Sq/TQ, Sk/TK), K-tiles innermost ("arbitrary"); VMEM carries the
running max/denominator/accumulator between K-tiles.  Fully-masked K-tiles
are skipped via ``pl.when`` on an index-range predicate — with a sliding
window this turns the quadratic sweep into O(Sq·window) work, the structural
analogue of MCBP's prediction-driven KV skipping for the *static* mask part.
GQA is handled in the index maps (query head h reads KV head h // group).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(
    q_ref,  # (1, TQ, D)
    k_ref,  # (1, TK, D)
    v_ref,  # (1, TK, D)
    out_ref,  # (1, TQ, D)
    m_ref,  # scratch (TQ, 128) f32
    l_ref,  # scratch (TQ, 128) f32
    acc_ref,  # scratch (TQ, D) f32
    *,
    scale: float,
    mask_kind: str,
    window: int,
    q_offset: int,
    tile_q: int,
    tile_k: int,
    k_tiles: int,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # block-level mask predicate: does this (iq, ik) tile contain any
    # unmasked entry?  q rows are offset by q_offset (cache continuation).
    q_lo = iq * tile_q + q_offset
    q_hi = q_lo + tile_q - 1
    k_lo = ik * tile_k
    k_hi = k_lo + tile_k - 1
    if mask_kind == "full":
        live = jnp.bool_(True)
    elif mask_kind == "causal":
        live = k_lo <= q_hi
    elif mask_kind == "sliding":
        live = (k_lo <= q_hi) & (k_hi >= q_lo - window + 1)
    elif mask_kind == "chunked":
        live = (k_lo <= q_hi) & (k_hi // window >= q_lo // window)
    else:
        raise ValueError(mask_kind)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (TQ, D)
        k = k_ref[0].astype(jnp.float32)  # (TK, D)
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (TQ, TK)

        qi = q_lo + jax.lax.broadcasted_iota(jnp.int32, (tile_q, tile_k), 0)
        kj = k_lo + jax.lax.broadcasted_iota(jnp.int32, (tile_q, tile_k), 1)
        if mask_kind == "causal":
            mask = kj <= qi
        elif mask_kind == "sliding":
            mask = (kj <= qi) & (qi - kj < window)
        elif mask_kind == "chunked":
            mask = (kj <= qi) & (qi // window == kj // window)
        else:
            mask = jnp.ones((tile_q, tile_k), bool)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]  # (TQ, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)  # (TQ, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # (TQ, TK)
        correction = jnp.exp(m_prev - m_new)  # (TQ, 1)
        l_new = l_prev * correction + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * correction + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == k_tiles - 1)
    def _flush():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        out_ref[0, ...] = (acc_ref[...] / l).astype(out_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,  # (BHq, Sq, D)  — batch*query-heads flattened
    k: jax.Array,  # (BHk, Sk, D)
    v: jax.Array,  # (BHk, Sk, D)
    *,
    group: int,  # Hq // Hk
    scale: float,
    mask_kind: str = "causal",
    window: int = 0,
    q_offset: int = 0,
    tile_q: int = 128,
    tile_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    assert Sq % tile_q == 0 and Sk % tile_k == 0, (Sq, Sk, tile_q, tile_k)
    grid = (BH, Sq // tile_q, Sk // tile_k)
    kernel = functools.partial(
        _kernel,
        scale=scale,
        mask_kind=mask_kind,
        window=window,
        q_offset=q_offset,
        tile_q=tile_q,
        tile_k=tile_k,
        k_tiles=Sk // tile_k,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tile_q, D), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, tile_k, D), lambda bh, iq, ik: (bh // group, ik, 0)),
            pl.BlockSpec((1, tile_k, D), lambda bh, iq, ik: (bh // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile_q, D), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((tile_q, 128), jnp.float32),
            pltpu.VMEM((tile_q, 128), jnp.float32),
            pltpu.VMEM((tile_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
