"""BGPP round-scoring Pallas kernel (paper §4.5's bit-serial adder trees).

One BGPP round = a masked bit-plane inner product: for every still-alive key,
score += q · ((1 − 2·sign) ⊙ plane_bits).  Keys are tiled along S; a tile
whose alive-count is zero skips both the HBM plane fetch *and* the compute —
the kernel-level realization of the paper's early termination (rejected keys'
remaining planes are never touched) and the clock-gating of idle adder trees.

The plane/sign inputs are the bit-planar packed KV cache (1 bit per element,
8:1 in uint8), so the per-round HBM traffic is exactly the paper's model:
D/8 bytes per alive key per round.
"""

from __future__ import annotations

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


def _kernel(q_ref, plane_ref, sign_ref, alive_ref, any_ref, out_ref):
    @pl.when(any_ref[0] == 0)
    def _skip():  # whole tile rejected earlier: no fetch, no adds
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(any_ref[0] != 0)
    def _score():
        bits = _unpack_bits_i32(plane_ref[...])  # (TS, D)
        sign = _unpack_bits_i32(sign_ref[...])
        signed = jnp.where(sign != 0, -bits, bits)
        q = q_ref[0].astype(jnp.int32)  # (D,)
        contrib = jnp.sum(signed * q[None, :], axis=1, keepdims=True)  # (TS,1)
        alive = alive_ref[...]  # (TS, 1) int32
        out_ref[...] = jnp.where(alive != 0, contrib, 0).astype(out_ref.dtype)


def bgpp_score_pallas(
    q: jax.Array,  # (1, D) int32
    plane_packed: jax.Array,  # (S, D//8) uint8
    sign_packed: jax.Array,  # (S, D//8) uint8
    alive: jax.Array,  # (S, 1) int32
    tile_any: jax.Array,  # (S//TS,) int32 — per-tile alive flags
    *,
    tile_s: int = 256,
    interpret: bool = False,
) -> jax.Array:
    S, Dp = plane_packed.shape
    assert S % tile_s == 0
    grid = (S // tile_s,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Dp * 8), lambda s: (0, 0)),
            pl.BlockSpec((tile_s, Dp), lambda s: (s, 0)),
            pl.BlockSpec((tile_s, Dp), lambda s: (s, 0)),
            pl.BlockSpec((tile_s, 1), lambda s: (s, 0)),
            pl.BlockSpec((1,), lambda s: (s,), memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((tile_s, 1), lambda s: (s, 0)),
        out_shape=jax.ShapeDtypeStruct((S, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(q, plane_packed, sign_packed, alive, tile_any)
