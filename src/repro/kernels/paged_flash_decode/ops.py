"""Public wrapper + dispatch routing for the paged flash decode family.

Build-time validation lives here (ISSUE-7 satellite: shape/divisibility
mistakes must raise actionable errors at the call boundary, not surface as
Pallas lowering failures deep inside Mosaic).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.paged_flash_decode.kernel import paged_flash_decode_pallas
from repro.kernels.paged_flash_decode.ref import paged_flash_decode_ref


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def _paged_flash_pallas_path(
    q, k, v, page_ids, pos, layer, *,
    page_size: int,
    k_scale=None, v_scale=None,
    interpret: bool = False,
):
    return paged_flash_decode_pallas(
        q, k, v, page_ids, pos, layer, page_size=page_size,
        k_scale=k_scale, v_scale=v_scale, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("page_size",))
def _paged_flash_ref_jit(q, k, v, page_ids, pos, layer, *, page_size,
                         k_scale=None, v_scale=None):
    return paged_flash_decode_ref(
        q, k, v, page_ids, pos, layer, page_size=page_size,
        k_scale=k_scale, v_scale=v_scale,
    )


def _paged_flash_ref_path(q, k, v, page_ids, pos, layer, *, page_size,
                          k_scale=None, v_scale=None):
    return _paged_flash_ref_jit(
        q, k, v, page_ids, pos, layer, page_size=page_size,
        k_scale=k_scale, v_scale=v_scale,
    )


def _validate(q, k, v, page_ids, pos, page_size, k_scale, v_scale):
    if q.ndim != 4:
        raise ValueError(
            f"paged_flash_decode: q must be grouped (B, Hk, g, D), got "
            f"shape {q.shape} — reshape (B, Hq, D) queries with "
            f"g = num_heads // num_kv_heads first"
        )
    B, Hk, g, D = q.shape
    if k.ndim != 3 or k.shape != v.shape:
        raise ValueError(
            f"paged_flash_decode: K/V must be layer stacks of token rows "
            f"(L, n_tok, Hk*D); got k {k.shape} vs v {v.shape}"
        )
    L, n_tok = k.shape[:2]
    if k.shape[2] != Hk * D:
        raise ValueError(
            f"paged_flash_decode: q carries Hk={Hk} kv heads of D={D} but "
            f"the pool rows hold {k.shape[2]} lanes, not Hk*D — under "
            f"shard_map both operands must be the SAME device-local head "
            f"shard"
        )
    if page_size < 1 or n_tok % page_size:
        raise ValueError(
            f"paged_flash_decode: pool of {n_tok} token rows is not a whole "
            f"number of pages of page_size={page_size}"
        )
    if page_ids.ndim != 2 or page_ids.shape[0] != B or pos.shape != (B,):
        raise ValueError(
            f"paged_flash_decode: page_ids must be (B={B}, pages_per_slot) "
            f"and pos (B,); got {page_ids.shape} / {pos.shape}"
        )
    if (k_scale is None) != (v_scale is None):
        raise ValueError(
            "paged_flash_decode: int8 pools need BOTH k_scale and v_scale "
            "— got exactly one"
        )
    S = page_ids.shape[1] * page_size
    forms = ((L, n_tok, Hk), (L, B, Hk, S))
    for s in (k_scale, v_scale):
        if s is not None and s.shape not in forms:
            raise ValueError(
                f"paged_flash_decode: scales must be token-major pools "
                f"{forms[0]} or heads-major per-slot rows {forms[1]}; got "
                f"{s.shape}"
            )


def paged_flash_decode(
    q: jax.Array,  # (B, Hk, g, D) f32 grouped decode query
    k: jax.Array,  # (L, n_tok, Hk*D) every layer's token rows
    v: jax.Array,  # (L, n_tok, Hk*D)
    page_ids: jax.Array,  # (B, pages_per_slot) int32, -1 = unmapped
    pos: jax.Array,  # (B,) int32 last valid logical position per slot
    layer,  # int or () int32: the layer of the stack to attend
    *,
    page_size: int,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    interpret: bool = False,
    mode: Optional[str] = None,
) -> jax.Array:
    """Page-table-aware single-token flash decode of layer ``layer`` of the
    stacked cache -> f32 ``(B, Hk, g, D)``.

    bf16/f32 pools run the dense attend; passing ``k_scale``/``v_scale``
    selects the int8 A2/A3 path, the scales as token-major pools
    ``(L, n_tok, Hk)`` or heads-major per-slot rows ``(L, B, Hk, S)``.
    Routing between compiled / interpret / ref is governed by
    :mod:`repro.kernels.dispatch`.
    """
    _validate(q, k, v, page_ids, pos, page_size, k_scale, v_scale)
    return dispatch.pallas_dispatch(
        "paged_flash_decode",
        _paged_flash_pallas_path,
        _paged_flash_ref_path,
        q, k, v, page_ids, pos, jnp.asarray(layer, jnp.int32),
        page_size=page_size,
        k_scale=k_scale,
        v_scale=v_scale,
        mode=mode,
        interpret=interpret,
    )
