"""BRCR GEMM Pallas kernel (paper §3.1 + Fig. 14, TPU-adapted per DESIGN.md §2).

Dataflow per (row-tile i, col-tile j) output block, iterating signed planes p
and K-tiles kt on the inner ("arbitrary") grid dims:

  1. load the group-pattern tile ``idx`` (TG × TK, TG = TM/m group rows) —
     this is the CAM content; patterns are the search keys;
  2. *match + merge*: one-hot(idx) forms the (TG·2^m × TK) indicator the MXU
     contracts against the activation tile → MAV ``Z`` (TG × 2^m × TN).
     The MXU enumerates all 2^m search keys at once — the paper's CAM sweep;
  3. *reconstruct*: ``E @ Z`` (E = m × 2^m enumeration matrix, fixed operand
     kept in VMEM — the RU's fixed datapath);
  4. accumulate ``±2^p``-weighted results into the f32 VMEM accumulator.

Tile-level sparsity: a host-precomputed ``tile_any`` bitmap marks (p, i, kt)
tiles whose patterns are all zero (pattern 0 contributes nothing because
E[:, 0] = 0); those tiles skip the MXU work entirely via ``pl.when`` — the
MXU-compatible form of the paper's zero-column elimination.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    idx_ref,  # (1, TG, TK) uint8 group patterns for plane p
    pw_ref,  # (1, 1) f32 plane weight ±2^p   (SMEM)
    any_ref,  # (1, 1, 1) int32 tile-nonzero flag (SMEM)
    x_ref,  # (TK, TN) activations
    out_ref,  # (TM, TN)
    acc_ref,  # scratch (TM, TN) f32
    *,
    m: int,
    n_planes: int,
    k_tiles: int,
):
    p = pl.program_id(2)
    kt = pl.program_id(3)

    @pl.when((p == 0) & (kt == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(any_ref[0, 0, 0] != 0)
    def _compute():
        idx = idx_ref[0].astype(jnp.int32)  # (TG, TK)
        tg, tk = idx.shape
        nbins = 2**m
        # one-hot over bins: (TG, 2^m, TK) — the CAM match bitmaps
        bins = jax.lax.broadcasted_iota(jnp.int32, (tg, nbins, tk), 1)
        onehot = (idx[:, None, :] == bins).astype(x_ref.dtype)
        # MAV: merge activations per pattern (addition-merge units)
        z = jax.lax.dot_general(
            onehot.reshape(tg * nbins, tk),
            x_ref[...],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (TG*2^m, TN)
        # reconstruction: Y_g = E @ Z_g  (fixed-datapath RU).  E is built
        # in-register from iota: E[j, c] = bit j of c.
        cc = jax.lax.broadcasted_iota(jnp.int32, (m, nbins), 1)
        jj = jax.lax.broadcasted_iota(jnp.int32, (m, nbins), 0)
        e = ((cc >> jj) & 1).astype(x_ref.dtype)  # (m, 2^m)
        z = z.reshape(tg, nbins, -1)
        y = jax.lax.dot_general(
            z,
            e,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (TG, TN, m)
        y = jnp.transpose(y, (0, 2, 1)).reshape(acc_ref.shape)  # (TM, TN)
        acc_ref[...] += pw_ref[0, 0] * y

    @pl.when((p == n_planes - 1) & (kt == k_tiles - 1))
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def brcr_gemm_pallas(
    group_idx: jax.Array,  # (P, G, H) uint8
    plane_weights: jax.Array,  # (P,) f32
    tile_any: jax.Array,  # (P, M//TM, H//TK) int32
    x: jax.Array,  # (H, N)
    *,
    m: int,
    tile_m: int = 128,
    tile_k: int = 256,
    tile_n: int = 128,
    interpret: bool = False,
) -> jax.Array:
    P, G, H = group_idx.shape
    M = G * m
    N = x.shape[1]
    assert M % tile_m == 0 and H % tile_k == 0 and N % tile_n == 0, (M, H, N)
    tg = tile_m // m
    grid = (M // tile_m, N // tile_n, P, H // tile_k)

    kernel = functools.partial(
        _kernel, m=m, n_planes=P, k_tiles=H // tile_k
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tg, tile_k), lambda i, j, p, kt: (p, i, kt)),
            pl.BlockSpec(
                (1, 1), lambda i, j, p, kt: (p, 0), memory_space=pltpu.SMEM
            ),
            pl.BlockSpec(
                (1, 1, 1),
                lambda i, j, p, kt: (p, i, kt),
                memory_space=pltpu.SMEM,
            ),
            pl.BlockSpec((tile_k, tile_n), lambda i, j, p, kt: (kt, j)),
        ],
        out_specs=pl.BlockSpec((tile_m, tile_n), lambda i, j, p, kt: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tile_m, tile_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(group_idx, plane_weights.reshape(P, 1), tile_any, x)


