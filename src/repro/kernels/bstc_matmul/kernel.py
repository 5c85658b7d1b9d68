"""Fused BSTC-decompress → dense int8 MXU matmul (the TPU-native path).

This kernel is the *beyond-paper* TPU realization of BSTC (DESIGN.md §2):
weights live in HBM in two-state-coded bit-plane form (the traffic win), a
weight tile is reconstructed to int8 inside VMEM, and a single dense MXU
matmul consumes it (the compute win — the MXU runs at full rate on dense
int8, unlike the ASIC's adder arrays which profit from skipped adds).

Per (i, j, kt) tile:
  mag  = Σ_p  decode_p(tile) << p      p over encoded planes (prefix-sum
                                       gather, same as bstc_decode) and raw
                                       planes (bit unpack)
  w    = (1 − 2·sign) · mag            sign-magnitude, |w| ≤ 127
  acc += w @ x_tile                    MXU, f32 accumulation

Each encoded plane keeps its own pattern capacity (padded to its max row
nnz), so HBM traffic per weight tile ≈ compressed bytes (bitmap + patterns)
instead of TM·TK int8 bytes — decode-stage GEMV time ÷ CR when memory-bound.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _unpack_bits_i32(packed: jax.Array) -> jax.Array:
    x = packed.astype(jnp.int32)
    shape = x.shape[:-1] + (x.shape[-1], 8)
    shifts = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    bits = (x[..., None] >> shifts) & 1
    return bits.reshape(x.shape[:-1] + (x.shape[-1] * 8,))


def _decode_tile(bitmap_tile, offs_tile, patterns_tile, m: int, tile_m: int):
    """Two-state decode -> expanded rows: (TM, TK) int32 bits of this plane."""
    bits = _unpack_bits_i32(bitmap_tile)  # (TGr, TK)
    pos = jnp.cumsum(bits, axis=1) - 1 + offs_tile  # (TGr, TK)
    pos = jnp.clip(pos, 0, patterns_tile.shape[1] - 1)
    vals = jnp.take_along_axis(patterns_tile.astype(jnp.int32), pos, axis=1)
    patt = jnp.where(bits != 0, vals, 0)  # (TGr, TK)
    tgr, tk = patt.shape
    # expand the m-bit column pattern back to m weight rows
    shifts = jax.lax.broadcasted_iota(jnp.int32, (tgr, m, tk), 1)
    rows = (patt[:, None, :] >> shifts) & 1
    return rows.reshape(tile_m, tk)


def _make_kernel(
    enc_planes: Sequence[int],
    raw_planes: Sequence[int],
    m: int,
    tile_m: int,
    k_tiles: int,
):
    n_enc = len(enc_planes)
    n_raw = len(raw_planes)

    def kernel(*refs):
        enc_refs = refs[: 3 * n_enc]
        raw_refs = refs[3 * n_enc : 3 * n_enc + n_raw]
        sign_ref, x_ref, out_ref, acc_ref = refs[3 * n_enc + n_raw :]
        kt = pl.program_id(2)

        @pl.when(kt == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        mag = jnp.zeros((tile_m, x_ref.shape[0]), jnp.int32)
        for e, p in enumerate(enc_planes):
            bm, offs, patt = enc_refs[3 * e : 3 * e + 3]
            rows = _decode_tile(bm[...], offs[...], patt[...], m, tile_m)
            mag += rows << p
        for r, p in enumerate(raw_planes):
            mag += _unpack_bits_i32(raw_refs[r][...]) << p
        sign = _unpack_bits_i32(sign_ref[...])
        w = jnp.where(sign != 0, -mag, mag).astype(x_ref.dtype)  # (TM, TK)
        acc_ref[...] += jax.lax.dot_general(
            w,
            x_ref[...],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        @pl.when(kt == k_tiles - 1)
        def _flush():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    return kernel


def bstc_matmul_pallas(
    enc_operands: Sequence[jax.Array],  # flat [bitmap_p, offsets_p, patterns_p]*
    raw_operands: Sequence[jax.Array],  # [(M, H//8) uint8] per raw plane
    sign_bits: jax.Array,  # (M, H//8) uint8
    x: jax.Array,  # (H, N)
    *,
    enc_planes: Sequence[int],
    raw_planes: Sequence[int],
    m: int,
    M: int,
    tile_m: int = 128,
    tile_n: int = 128,
    interpret: bool = False,
) -> jax.Array:
    H = sign_bits.shape[1] * 8
    N = x.shape[1]
    n_enc = len(enc_planes)
    if n_enc:
        tile_k = H // enc_operands[1].shape[1]
    else:
        tile_k = min(H, 512)
    assert M % tile_m == 0 and N % tile_n == 0 and H % tile_k == 0
    tgr = tile_m // m
    grid = (M // tile_m, N // tile_n, H // tile_k)

    in_specs = []
    for e in range(n_enc):
        cap = enc_operands[3 * e + 2].shape[1]
        in_specs += [
            pl.BlockSpec((tgr, tile_k // 8), lambda i, j, kt: (i, kt)),
            pl.BlockSpec((tgr, 1), lambda i, j, kt: (i, kt)),
            pl.BlockSpec((tgr, cap), lambda i, j, kt: (i, 0)),
        ]
    for _ in raw_planes:
        in_specs.append(pl.BlockSpec((tile_m, tile_k // 8), lambda i, j, kt: (i, kt)))
    in_specs.append(pl.BlockSpec((tile_m, tile_k // 8), lambda i, j, kt: (i, kt)))
    in_specs.append(pl.BlockSpec((tile_k, tile_n), lambda i, j, kt: (kt, j)))

    kernel = _make_kernel(
        tuple(enc_planes), tuple(raw_planes), m, tile_m, H // tile_k
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tile_m, tile_n), lambda i, j, kt: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tile_m, tile_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*enc_operands, *raw_operands, sign_bits, x)
