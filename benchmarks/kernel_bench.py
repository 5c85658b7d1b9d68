"""Kernel micro-benchmarks (interpret-mode wall clock is NOT TPU time; the
derived column carries the structural numbers that transfer: HBM bytes
moved, compression ratios, op counts)."""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from benchmarks.common import emit, time_fn
from repro.kernels.brcr_gemm import brcr_gemm, prepare_brcr_operands
from repro.kernels.bstc_matmul import bstc_matmul, prepare_bstc_matmul_operands
from repro.kernels.bgpp_paged_attend import bgpp_paged_attend
from repro.kernels.paged_flash_decode import paged_flash_decode
from repro.serving import kv_cache as kvc
from repro.utils.synthetic import synthetic_llm_weight_int8


def run():
    rng = np.random.default_rng(8)
    M, H, N = 64, 1024, 32
    w_q, scale = synthetic_llm_weight_int8(rng, (M, H))
    x = jnp.asarray(rng.normal(size=(H, N)), jnp.float32)

    ops_brcr = prepare_brcr_operands(w_q, m=4)
    us = time_fn(
        lambda: brcr_gemm(ops_brcr, x, tile_m=32, tile_k=256, tile_n=32,
                          interpret=True),
        iters=3, warmup=1,
    )
    idx_bytes = ops_brcr.group_idx.size
    emit("kernel_brcr_gemm_interp", us,
         f"M{M}xH{H}xN{N};idx_bytes={idx_bytes}")

    ops_bstc = prepare_bstc_matmul_operands(w_q, scale, tile_k=512)
    us = time_fn(
        lambda: bstc_matmul(ops_bstc, x, tile_m=32, tile_n=32, interpret=True),
        iters=3, warmup=1,
    )
    emit(
        "kernel_bstc_matmul_interp", us,
        f"hbm_bytes={ops_bstc.hbm_bytes};dense_bytes={ops_bstc.dense_bytes};"
        f"CR={ops_bstc.compression_ratio:.3f}",
    )

    # ISSUE-7 paged-attention families: interpret-mode kernel vs the jnp
    # oracle on identical operands (the structural derived numbers — bytes
    # per head, keep budget — are what transfer to TPU, not CPU emulation
    # wall clock).
    B, Hk, g, Dh, S, page = 2, 2, 3, 32, 64, 8
    k = jnp.asarray(rng.normal(size=(B * S, Hk, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B * S, Hk, Dh)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, Hk, g, Dh)), jnp.float32)
    table = jnp.asarray(
        rng.permutation(B * S // page).reshape(B, S // page).astype(np.int32)
    )
    pos = jnp.full((B,), S - 1, jnp.int32)
    k_q, ks = kvc.quantize_kv(k)
    v_q, vs = kvc.quantize_kv(v)
    for mode, tag in (("interpret", "interp"), ("ref", "ref")):
        us = time_fn(
            lambda m=mode: paged_flash_decode(
                q, k_q.reshape(1, B * S, Hk * Dh),
                v_q.reshape(1, B * S, Hk * Dh), table, pos, 0,
                page_size=page, k_scale=ks[None], v_scale=vs[None], mode=m,
            ),
            iters=3, warmup=1,
        )
        emit(f"kernel_paged_flash_decode_int8_{tag}", us,
             f"B{B}xHk{Hk}xg{g}xD{Dh};S={S};page={page}")

    planes, sign = kvc.k_to_bitplanes(k_q)
    phys = jnp.asarray(
        rng.permutation(B * S).reshape(B, S).astype(np.int32)
    )
    rounds, keep = 4, 0.25
    k_max = max(1, int(np.ceil(keep * S)))
    survivors = (S,) + tuple(max(k_max, S >> r) for r in range(1, rounds))
    for mode, tag in (("interpret", "interp"), ("ref", "ref")):
        us = time_fn(
            lambda m=mode: bgpp_paged_attend(
                q, planes, sign, ks, v_q, vs, phys, pos,
                rounds=rounds, k_max=k_max, survivors=survivors, mode=m,
            ),
            iters=3, warmup=1,
        )
        emit(f"kernel_bgpp_paged_attend_{tag}", us,
             f"B{B}xHk{Hk}xg{g}xD{Dh};S={S};rounds={rounds};k_max={k_max}")
