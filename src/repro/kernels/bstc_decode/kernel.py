"""BSTC plane-decode Pallas kernel (paper §4.4, TPU-adapted).

The ASIC's serial SIPO decoder becomes a fully-vectorized three-step pipeline
per (group-row-tile, H-tile):

  1. unpack the two-state indicator bitmap (1 bit per m-bit column) from its
     8:1 byte packing;
  2. prefix-sum addressing: position of column h's pattern in the packed
     non-zero stream = (host-precomputed tile base offset) + within-tile
     cumsum − 1 — the vector equivalent of the paper's segmented layout with
     per-sub-weight start addresses (Fig. 15c);
  3. gather the patterns and mask zero columns.

Output is the (G, H) *group pattern* tensor — exactly the BRCR kernel's
input, realizing the paper's "coding and computation at the same group
granularity" (no re-layout between decode and compute).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _unpack_bits_i32(packed: jax.Array) -> jax.Array:
    """(..., B) uint8 -> (..., 8B) int32 {0,1}; little-endian within bytes."""
    x = packed.astype(jnp.int32)
    shape = x.shape[:-1] + (x.shape[-1], 8)
    shifts = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    bits = (x[..., None] >> shifts) & 1
    return bits.reshape(x.shape[:-1] + (x.shape[-1] * 8,))


def _kernel(bitmap_ref, offs_ref, patterns_ref, out_ref):
    bits = _unpack_bits_i32(bitmap_ref[...])  # (TG, TK)
    pos = jnp.cumsum(bits, axis=1) - 1 + offs_ref[...]  # (TG, TK)
    pos = jnp.clip(pos, 0, patterns_ref.shape[1] - 1)
    vals = jnp.take_along_axis(patterns_ref[...].astype(jnp.int32), pos, axis=1)
    out_ref[...] = jnp.where(bits != 0, vals, 0).astype(out_ref.dtype)


def bstc_decode_pallas(
    bitmap: jax.Array,  # (G, H//8) uint8 packed indicators
    tile_offsets: jax.Array,  # (G, H//TK) int32 stream base per tile
    patterns: jax.Array,  # (G, cap) uint8 packed non-zero patterns
    *,
    tile_g: int = 8,
    tile_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    G, Hp = bitmap.shape
    H = Hp * 8
    assert H % tile_k == 0 and G % tile_g == 0, (G, H, tile_g, tile_k)
    cap = patterns.shape[1]
    grid = (G // tile_g, H // tile_k)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_g, tile_k // 8), lambda g, kt: (g, kt)),
            pl.BlockSpec((tile_g, 1), lambda g, kt: (g, kt)),
            pl.BlockSpec((tile_g, cap), lambda g, kt: (g, 0)),
        ],
        out_specs=pl.BlockSpec((tile_g, tile_k), lambda g, kt: (g, kt)),
        out_shape=jax.ShapeDtypeStruct((G, H), jnp.uint8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(bitmap, tile_offsets, patterns)
