"""Public wrapper + dispatch routing for the fused BGPP paged decode.

Build-time validation lives here (ISSUE-7 satellite: GQA/plan/shape
mistakes must raise actionable errors at the call boundary, not surface as
Pallas lowering failures deep inside Mosaic).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax

from repro.kernels import dispatch
from repro.kernels.bgpp_paged_attend.kernel import bgpp_paged_attend_pallas
from repro.kernels.bgpp_paged_attend.ref import NBITS, bgpp_paged_attend_ref


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "rounds", "k_max", "survivors", "interpret"),
)
def _bgpp_pallas_path(
    q, k_planes, k_sign, k_scale, v, v_scale, page_ids, pos, *,
    page_size: int, rounds: int, k_max: int, survivors: Tuple[int, ...],
    interpret: bool = False,
):
    return bgpp_paged_attend_pallas(
        q, k_planes, k_sign, k_scale, v, v_scale, page_ids, pos,
        page_size=page_size, rounds=rounds, k_max=k_max, survivors=survivors,
        interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("page_size", "rounds", "k_max", "survivors")
)
def _bgpp_ref_path(q, k_planes, k_sign, k_scale, v, v_scale, page_ids, pos,
                   *, page_size, rounds, k_max, survivors):
    return bgpp_paged_attend_ref(
        q, k_planes, k_sign, k_scale, v, v_scale, page_ids, pos,
        page_size=page_size, rounds=rounds, k_max=k_max, survivors=survivors,
    )


def _validate(q, k_planes, k_sign, k_scale, v, v_scale, page_ids, pos,
              page_size, rounds, k_max, survivors):
    if q.ndim != 4:
        raise ValueError(
            f"bgpp_paged_attend: q must be grouped (B, Hk, g, D), got shape "
            f"{q.shape} — reshape (B, Hq, D) queries with "
            f"g = num_heads // num_kv_heads first"
        )
    B, Hk, g, D = q.shape
    if D % 8:
        raise ValueError(
            f"bgpp_paged_attend: head_dim={D} is not a multiple of 8 — "
            f"packed bit planes need whole bytes per row"
        )
    if k_planes.ndim != 4 or k_planes.shape[0] != NBITS:
        raise ValueError(
            f"bgpp_paged_attend: k_planes must be (NBITS={NBITS}, n_tok, Hk, "
            f"D/8) packed magnitude planes; got {k_planes.shape}"
        )
    nbits, n_tok, hk_p, Dp = k_planes.shape
    if hk_p != Hk:
        raise ValueError(
            f"bgpp_paged_attend: q carries Hk={Hk} kv heads but the pool "
            f"carries {hk_p} — under shard_map both operands must be the "
            f"SAME device-local head shard"
        )
    if Dp != D // 8:
        raise ValueError(
            f"bgpp_paged_attend: packed plane width {Dp} != head_dim/8 = "
            f"{D // 8}"
        )
    if k_sign.shape != (n_tok, Hk, Dp):
        raise ValueError(
            f"bgpp_paged_attend: k_sign must be (n_tok, Hk, D/8) = "
            f"({n_tok}, {Hk}, {Dp}); got {k_sign.shape}"
        )
    if k_scale.shape != (n_tok, Hk) or v_scale.shape != (n_tok, Hk):
        raise ValueError(
            f"bgpp_paged_attend: scales must be (n_tok={n_tok}, Hk={Hk}); "
            f"got k_scale {k_scale.shape} / v_scale {v_scale.shape}"
        )
    if v.shape != (n_tok, Hk, D):
        raise ValueError(
            f"bgpp_paged_attend: v must be (n_tok, Hk, D) int8; got {v.shape}"
        )
    if page_ids.ndim != 2 or page_ids.shape[0] != B or pos.shape != (B,):
        raise ValueError(
            f"bgpp_paged_attend: page_ids must be (B={B}, pages_per_slot) "
            f"and pos (B,); got {page_ids.shape} / {pos.shape}"
        )
    if page_size < 1 or n_tok % page_size:
        raise ValueError(
            f"bgpp_paged_attend: pool of {n_tok} token rows is not a whole "
            f"number of pages of page_size={page_size}"
        )
    survivors = tuple(int(s) for s in survivors)
    S = survivors[0] if survivors else 0
    if -(-S // page_size) != page_ids.shape[1]:
        raise ValueError(
            f"bgpp_paged_attend: survivors[0]={S} must be the logical "
            f"context the page table covers (round 0 scans every position): "
            f"{page_ids.shape[1]} pages of {page_size} lanes hold "
            f"{(page_ids.shape[1] - 1) * page_size + 1}.."
            f"{page_ids.shape[1] * page_size} positions"
        )
    if len(survivors) != rounds:
        raise ValueError(
            f"bgpp_paged_attend: plan has rounds={rounds} but "
            f"{len(survivors)} survivor widths {survivors} — pass the tuple "
            f"from kv_cache.bgpp_decode_plan unmodified"
        )
    if any(survivors[i] < survivors[i + 1] for i in range(rounds - 1)):
        raise ValueError(
            f"bgpp_paged_attend: survivor widths must be non-increasing; "
            f"got {survivors}"
        )
    if not (1 <= k_max <= S) or k_max > survivors[-1]:
        raise ValueError(
            f"bgpp_paged_attend: k_max={k_max} must satisfy 1 <= k_max <= "
            f"min(S={S}, survivors[-1]={survivors[-1]})"
        )


def bgpp_paged_attend(
    q: jax.Array,  # (B, Hk, g, D) f32 RAW grouped decode query
    k_planes: jax.Array,  # (NBITS, n_tok, Hk, D/8) uint8 packed planes
    k_sign: jax.Array,  # (n_tok, Hk, D/8) uint8 packed sign plane
    k_scale: jax.Array,  # (n_tok, Hk) f32
    v: jax.Array,  # (n_tok, Hk, D) int8
    v_scale: jax.Array,  # (n_tok, Hk) f32
    page_ids: jax.Array,  # (B, pages_per_slot) int32, -1 = unmapped
    pos: jax.Array,  # (B,) int32 last valid logical position per slot
    *,
    page_size: int,
    rounds: int,
    k_max: int,
    survivors: Tuple[int, ...],
    interpret: bool = False,
    mode: Optional[str] = None,
) -> jax.Array:
    """Fused two-phase BGPP paged decode -> f32 ``(B, Hk, g, D)``.

    ``(rounds, k_max, survivors)`` is the static progressive plan from
    :func:`repro.serving.kv_cache.bgpp_decode_plan`; ``survivors[0]`` is
    the logical context ``S`` (round 0 scores every position below it).  Routing between
    compiled / interpret / ref is governed by :mod:`repro.kernels.dispatch`.
    """
    survivors = tuple(int(s) for s in survivors)
    _validate(q, k_planes, k_sign, k_scale, v, v_scale, page_ids, pos,
              page_size, rounds, k_max, survivors)
    return dispatch.pallas_dispatch(
        "bgpp_paged_attend",
        _bgpp_pallas_path,
        _bgpp_ref_path,
        q, k_planes, k_sign, k_scale, v, v_scale, page_ids, pos,
        page_size=page_size,
        rounds=rounds,
        k_max=k_max,
        survivors=survivors,
        mode=mode,
        interpret=interpret,
    )
