"""Gradient compression for data-parallel sync (bit-themed, like the paper).

``compressed_psum_mean`` runs inside ``shard_map``: each data shard
quantizes its local gradient to int8 (per-leaf absmax scale), the int8
payload is all-reduced (sum) over the data axis, and the result is
dequantized — 4× less cross-pod traffic than f32 (2× vs bf16) at the cost of
bounded quantization noise.  The scales themselves are psum'd (tiny).

``make_compressed_dp_grad_fn`` wraps a loss into an explicit-DP gradient
function with the compressed sync — used where the cross-pod links are the
bottleneck (§Perf knob); inside a pod, the partitioner's native reduce
stays f32.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


Tree = Any


def _q8_leaf(g: jax.Array):
    scale = jnp.maximum(jnp.max(jnp.abs(g)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_psum_mean(grads: Tree, axis_name: str) -> Tree:
    """int8-compressed mean-allreduce over ``axis_name`` (inside shard_map).

    Uses a *shared* scale: the per-leaf absmax is pmax'd first (a scalar
    collective, negligible traffic), every shard quantizes against it, the
    int8 payloads are summed in int32, and the result is dequantized.  The
    quantization error is then bounded by the global absmax regardless of
    shard-to-shard gradient scale skew.
    """
    n = jax.lax.psum(1, axis_name)

    def one(g):
        g32 = g.astype(jnp.float32)
        local_max = jnp.max(jnp.abs(g32))
        scale = jnp.maximum(jax.lax.pmax(local_max, axis_name), 1e-12) / 127.0
        q = jnp.clip(jnp.round(g32 / scale), -127, 127).astype(jnp.int8)
        # sum int8 payloads in int32 (no overflow for n <= 2^23 shards)
        qsum = jax.lax.psum(q.astype(jnp.int32), axis_name)
        return (qsum.astype(jnp.float32) * scale / n).astype(g.dtype)

    return jax.tree.map(one, grads)


def make_compressed_dp_grad_fn(
    loss_fn: Callable[[Tree, Tree], jax.Array],
    mesh,
    data_axis: str = "data",
) -> Callable[[Tree, Tree], Tree]:
    """Explicit data-parallel value+grad with int8 gradient sync.

    params replicated, batch sharded over ``data_axis``.  Returns
    f(params, batch) -> (loss, grads) with grads mean-reduced via the
    compressed collective.
    """

    def local(params, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        grads = compressed_psum_mean(grads, data_axis)
        loss = jax.lax.pmean(loss, data_axis)
        return loss, grads

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(data_axis)),
        out_specs=(P(), P()),
        check_vma=False,
    )
