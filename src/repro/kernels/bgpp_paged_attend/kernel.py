"""Pallas body for the fused two-phase BGPP paged decode.

One launch per decode step, grid ``(B, pages_per_slot)``: each slot's page
steps stage its physical pages (all kv heads at once) into VMEM through
the scalar-prefetched page table, and the last page step runs the WHOLE
two-phase pipeline for every head of the slot —

  1. round 0: score the sign + MSB magnitude plane of every logical lane,
     ``qf @ ((1-2*sign) * plane)^T * 2^(NBITS-1)`` (max over the g query
     rows of each kv head);
  2. progressive rounds: keep the ``survivors[r]`` best candidates, add the
     next plane's contribution ``* 2^(NBITS-1-r)``;
  3. keep the final ``k_max`` candidates and run the engine's exact int8
     A2/A3 attend over them.

Everything is lane-dense and gather-free, so Mosaic can lower it:

* The packed planes stay packed.  A pool row holds every head's ``D/8``
  bytes side by side (``L = Hk*D/8`` lanes, a free reshape of the
  token-major pool), and bit ``t`` of byte ``c`` is element ``8c + t``.
  The query arrives pre-split by bit position into block-diagonal
  ``(8, Hk*g, L)`` matrices (row ``h*g + i`` holds head ``h``'s query
  elements ``8c + t`` in head ``h``'s lanes), so a plane score is eight
  ``(Hk*g, L) x (S, L)^T`` dots over shifted-and-masked bytes.  Every
  operand is a small integer, so these dots are exact in f32.
* Candidate sets are lane masks, not index lists.  Top-k keeps the ``k``
  largest scores with the lowest logical index winning ties — the
  selection of ``lax.top_k`` followed by an ascending sort, which is what
  the engine and the oracle do.  It is found by bisection: the k-th
  largest integer score, then the index cutoff among the ties.
* The softmax denominator sums the survivors' exponentials compacted in
  ascending-index order (a one-hot dot), the oracle's reduction; all other
  steps are elementwise or exact integer dots, so interpret mode matches
  the oracle bit-for-bit.

Unlike the jnp oracle this kernel reads every plane of every lane of the
slot: the byte saving of the two-phase plan is a modeled count
(``kv_cache.decode_read_bytes``), not what this kernel transfers.  VMEM
holds one slot's pages at a time: ``S * Hk * (NBITS*D/8 + D/8 + D)`` bytes
of staged pool rows (2.0 MiB at S=1024, Hk=8, D=128).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_flash_decode.kernel import slot_scale_rows

NEG_INF = -1e30  # matches repro.core.attention.NEG_INF
# integer top-k keys: plane scores are exact integers of magnitude
# < 127 * 128 * 120 < 2**21, so these sentinels rank below every real score
_INVALID = -(1 << 22)  # lane past pos: ranks like the oracle's NEG_INF
_DROPPED = -(1 << 23)  # not a candidate this round: never selected
_TOP = 1 << 22  # strictly above every key


def _nt_dot(a, b, prefer, precision=None):
    """(M, K) x (N, K) -> (M, N), contracting K."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=prefer,
        precision=precision,
    )


def _count(mask):
    """Per-row count of a (rows, S) bool mask -> (rows, 1) int32."""
    return jnp.sum(mask.astype(jnp.int32), axis=1, keepdims=True)


def _select(key, k: int, lane):
    """Mask of the ``k`` largest keys per row, lowest lane winning ties.

    ``key`` (rows, S) int32; every row holds at least ``k`` keys above
    ``_DROPPED``.  Two bisections: the k-th largest key ``t``, then the
    lane cutoff that admits exactly the missing number of ``t``-ties.
    """
    rows, S = key.shape

    def by_value(_, lohi):  # count(key >= lo) >= k > count(key >= hi)
        lo, hi = lohi
        mid = (lo + hi) >> 1
        ok = _count(key >= mid) >= k
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    t, _ = jax.lax.fori_loop(
        0, 25, by_value,
        (jnp.full((rows, 1), _DROPPED, jnp.int32),
         jnp.full((rows, 1), _TOP, jnp.int32)),
    )
    above = key > t
    tie = key == t
    need = k - _count(above)

    def by_lane(_, lohi):  # count(tie & lane < lo) < need <= ... < hi)
        lo, hi = lohi
        mid = (lo + hi) >> 1
        ok = _count(tie & (lane < mid)) >= need
        return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

    _, cut = jax.lax.fori_loop(
        0, S.bit_length() + 1, by_lane,
        (jnp.zeros((rows, 1), jnp.int32), jnp.full((rows, 1), S, jnp.int32)),
    )
    return above | (tie & (lane < cut))


def _compact_sum(e, sel, lane, k: int):
    """Sum of ``e``'s (g, S) selected lanes in ascending-lane order, as the
    oracle sums its compacted (g, k) survivors: a one-hot dot gathers them
    (exact), then the same k-wide reduction runs."""
    x = sel.astype(jnp.int32)  # (1, S)
    incl = x
    shift = 1
    while shift < x.shape[1]:  # log-step inclusive prefix count
        incl = incl + jnp.where(
            lane >= shift, pltpu.roll(incl, shift, 1), 0
        )
        shift *= 2
    slot = jax.lax.broadcasted_iota(jnp.int32, (k, x.shape[1]), 0)
    onehot = (sel & (incl - x == slot)).astype(jnp.float32)  # (k, S)
    packed = _nt_dot(e, onehot, jnp.float32,
                     precision=jax.lax.Precision.HIGHEST)  # (g, k)
    return jnp.sum(packed, axis=-1, keepdims=True)


def _body(pids_ref, pos_ref, qf_ref, qq_ref, qs_ref, planes_ref, sign_ref,
          ks_ref, vs_ref, v_ref, out_ref, planes_buf, sign_buf, v_buf,
          score_buf, li_buf, sel_buf, *, rounds: int, k_max: int,
          survivors: Tuple[int, ...], nbits: int, g: int, page_size: int,
          scale: float):
    b, j = pl.program_id(0), pl.program_id(1)
    rows = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
    planes_buf[:, rows, :] = planes_ref[...]
    sign_buf[rows, :] = sign_ref[...]
    v_buf[rows, :] = v_ref[...]

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        Hk, S = score_buf.shape
        D = v_buf.shape[1] // Hk
        lane = jax.lax.broadcasted_iota(jnp.int32, (Hk, S), 1)
        valid = lane <= pos_ref[b]
        sign = sign_buf[...].astype(jnp.int32)  # (S, L) packed bytes

        def plane_partial(p):  # (Hk*g, S): plane p's signed score
            packed = planes_buf[p].astype(jnp.int32)

            def bit_dot(t, acc):  # exact: small integers throughout
                bit = ((packed >> t) & 1).astype(jnp.float32)
                signed = jnp.where(((sign >> t) & 1) != 0, -bit, bit)
                return acc + _nt_dot(qf_ref[t], signed, jnp.float32)

            acc = jax.lax.fori_loop(
                0, 8, bit_dot, jnp.zeros((Hk * g, S), jnp.float32)
            )
            return acc * float(2**p)

        def head_max(partial):  # GQA union: max over each head's g rows
            for h in range(Hk):
                score_buf[h:h + 1, :] = jnp.max(
                    partial[h * g:(h + 1) * g], axis=0, keepdims=True
                )
            return score_buf[...]

        def keys(score, cand):
            return jnp.where(
                cand, jnp.where(valid, score.astype(jnp.int32), _INVALID),
                _DROPPED,
            )

        # ---- phase 1: progressive plane rounds over candidate masks ------
        partial = plane_partial(nbits - 1)
        cand = lane < survivors[0]
        for r in range(1, rounds):
            cand = _select(keys(head_max(partial), cand), survivors[r], lane)
            partial = partial + plane_partial(nbits - 1 - r)
        sel = _select(keys(head_max(partial), cand), k_max, lane)

        # ---- phase 2: exact int8 attend over the k_max survivors ---------
        def key_dot(t, acc):  # K's elements 8c + t, rebuilt from planes
            def add_plane(p, mag):
                return mag + (((planes_buf[p].astype(jnp.int32) >> t) & 1) << p)

            mag = jax.lax.fori_loop(
                0, nbits, add_plane, jnp.zeros(sign.shape, jnp.int32)
            )
            k_t = jnp.where(((sign >> t) & 1) != 0, -mag, mag).astype(jnp.int8)
            return acc + _nt_dot(qq_ref[t], k_t, jnp.int32)

        logits_i = jax.lax.fori_loop(
            0, 8, key_dot, jnp.zeros((Hk * g, S), jnp.int32)
        )

        for h in range(Hk):  # stage per-head rows for the head loop below
            li_buf[h] = logits_i[h * g:(h + 1) * g]
        sel_buf[...] = sel.astype(jnp.int32)

        def attend_head(h, carry):
            sel_h = sel_buf[pl.ds(h, 1), :] != 0  # (1, S)
            logits = (li_buf[h].astype(jnp.float32) * qs_ref[h]
                      * ks_ref[pl.ds(h, 1), :] * scale)
            logits = jnp.where(sel_h & valid[:1], logits, NEG_INF)
            m = jnp.max(logits, axis=-1, keepdims=True)
            e = jnp.exp(logits - m)
            probs = e / _compact_sum(e, sel_h, lane[:1], k_max)
            w = probs * vs_ref[pl.ds(h, 1), :]
            w_scale = jnp.maximum(
                jnp.max(w, axis=-1, keepdims=True), 1e-20
            ) / 127.0
            w_q = jnp.clip(jnp.round(w / w_scale), 0, 127).astype(jnp.int8)
            v_h = v_buf[:, pl.ds(pl.multiple_of(h * D, D), D)]
            pv = jax.lax.dot_general(
                w_q, v_h, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            out_ref[h] = pv.astype(jnp.float32) * w_scale
            return carry

        jax.lax.fori_loop(0, Hk, attend_head, 0)


def _bit_major(x: jax.Array) -> jax.Array:
    """(B, Hk, g, D) query -> (B, 8, Hk*g, Hk*D/8) block-diagonal by bit
    position: ``[b, t, h*g + i, h*D/8 + c] = x[b, h, i, 8c + t]``."""
    B, Hk, g, D = x.shape
    xt = x.reshape(B, Hk, g, D // 8, 8).transpose(0, 4, 1, 2, 3)
    eye = jnp.eye(Hk, dtype=bool)[None, None, :, None, :, None]
    out = jnp.where(eye, xt[:, :, :, :, None, :], jnp.zeros((), x.dtype))
    return out.reshape(B, 8, Hk * g, Hk * D // 8)


def bgpp_paged_attend_pallas(
    q: jax.Array,  # (B, Hk, g, D) f32 RAW grouped decode query
    k_planes: jax.Array,  # (NBITS, n_tok, Hk, D/8) uint8
    k_sign: jax.Array,  # (n_tok, Hk, D/8) uint8
    k_scale: jax.Array,  # (n_tok, Hk) f32
    v: jax.Array,  # (n_tok, Hk, D) int8
    v_scale: jax.Array,  # (n_tok, Hk) f32
    page_ids: jax.Array,  # (B, pages_per_slot) int32
    pos: jax.Array,  # (B,) int32
    *,
    page_size: int,
    rounds: int,
    k_max: int,
    survivors: Tuple[int, ...],
    query_bits: int = 4,
    interpret: bool = False,
) -> jax.Array:
    """Launch the fused BGPP decode kernel -> f32 ``(B, Hk, g, D)``."""
    B, Hk, g, D = q.shape
    nbits, n_tok, _, Dp = k_planes.shape
    P = page_size
    pp = page_ids.shape[1]
    S = pp * P
    L = Hk * Dp
    # the engine's query prep (``_bgpp_quant_query`` / the int8 attend's
    # q quantization), split by bit position for the packed-plane dots
    qg = q.astype(jnp.float32)
    dq = jnp.maximum(jnp.max(jnp.abs(qg), axis=-1, keepdims=True), 1e-8) / 127.0
    q_int = jnp.clip(jnp.round(qg / dq), -127, 127).astype(jnp.int32)
    shift = max(nbits - query_bits, 0)  # core.bgpp._truncate_query
    qf = jnp.sign(q_int) * ((jnp.abs(q_int) >> shift) << shift)
    q_q = q_int.astype(jnp.int8)  # same scale, same rounding as qf's source
    pids = jnp.maximum(page_ids, 0).astype(jnp.int32)

    slot4 = lambda b, j, pids_, pos_: (b, 0, 0, 0)
    slot3 = lambda b, j, pids_, pos_: (b, 0, 0)
    page2 = lambda b, j, pids_, pos_: (pids_[b, j], 0)
    rows_of = lambda x: slot_scale_rows(x, page_ids, P)  # (B, Hk, S)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, pp),
        in_specs=[
            pl.BlockSpec((None, 8, Hk * g, L), slot4),
            pl.BlockSpec((None, 8, Hk * g, L), slot4),
            pl.BlockSpec((None, Hk, g, 1), slot4),
            pl.BlockSpec((nbits, P, L),
                         lambda b, j, pids_, pos_: (0, pids_[b, j], 0)),
            pl.BlockSpec((P, L), page2),
            pl.BlockSpec((None, Hk, S), slot3),
            pl.BlockSpec((None, Hk, S), slot3),
            pl.BlockSpec((P, Hk * D), page2),
        ],
        out_specs=pl.BlockSpec((None, Hk, g, D), slot4),
        scratch_shapes=[
            pltpu.VMEM((nbits, S, L), k_planes.dtype),
            pltpu.VMEM((S, L), k_sign.dtype),
            pltpu.VMEM((S, Hk * D), v.dtype),
            pltpu.VMEM((Hk, S), jnp.float32),
            pltpu.VMEM((Hk, g, S), jnp.int32),
            pltpu.VMEM((Hk, S), jnp.int32),
        ],
    )
    body = functools.partial(
        _body, rounds=rounds, k_max=k_max, survivors=tuple(survivors),
        nbits=nbits, g=g, page_size=P, scale=D**-0.5,
    )
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hk, g, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="bgpp_paged_attend",
    )(pids, pos.astype(jnp.int32),
      _bit_major(qf.astype(jnp.float32)), _bit_major(q_q),
      dq,
      k_planes.reshape(nbits, n_tok, L), k_sign.reshape(n_tok, L),
      rows_of(k_scale), rows_of(v_scale), v.reshape(n_tok, Hk * D))
