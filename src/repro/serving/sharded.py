"""Mesh placement for the serving stack.

Serving meshes are ``(data, model)``.  ``"data"`` splits the batch of
slots: each data group decodes its own rows with whole weights, so a
data-only mesh computes exactly what one device computes.  ``"model"``
splits every layer, Megatron style (:func:`serving_param_specs`):

* **column-parallel** — ``wq``/``wk``/``wv`` and the MLP's ``gate``/``up``
  carry HEADS/KV_HEADS/FF on their output (last) axis: each device
  computes its heads' and its FF slice's activations from the whole
  d_model row, no collective.
* **the attend** — with q/k/v and the KV stacks or pools sharded on the
  same head axis, every score, softmax and weighted sum stays on the
  device that holds the head.
* **row-parallel** — ``wo`` carries HEADS and the MLP's ``down`` FF on
  their input (first) axis, so each device multiplies its own heads' or
  FF slice's activations by its own rows and holds a partial sum of the
  whole output.  One all-reduce per sublayer sums them
  (``engine._row_parallel``: two per layer, ``tp.attn_out`` and
  ``tp.mlp_out``).
* **vocabulary** — the embedding's and ``lm_head``'s vocabulary axis: a
  sharded token lookup is a masked gather and an exact ``x + 0`` combine;
  the logits leave the step sharded by column.

Norms stay replicated.  Every device holds ``1/model`` of every matrix.

What stays exact: on a ``"model"`` axis of size 1 every one of these
splits is the whole array, so every ``[1, 1]`` and data-only mesh holds
whole weights on every device and decodes bit-identically to one device.
What does not: with ``model > 1`` the two all-reduces of each layer add
``model`` float partial sums, each a contraction over ``1/model`` of the
input, where one device adds the whole contraction in one order.
Float addition is not associative, so the outputs differ from a single
device's by rounding: in float32 a few ulps of the sum, which the sharded
parity oracle (``tests/test_serving_fuzz.py``) bounds.  Replicating
``wo`` and the MLP instead (the earlier, exact placement) leaves each
device three quarters of a 7B model's weights on a 1x4 mesh and no room
for its cache, and reads them all every step.

This module derives the placement from the model zoo's logical specs and
owns the host->device placement of params and cache
(:func:`shard_params` / :func:`init_cache` / :func:`shard_cache`) plus
the mesh bookkeeping the scheduler's read accounting reports
(:func:`mesh_shape`, :func:`projection_shards`).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AbstractMesh, NamedSharding, PartitionSpec as P

from repro.distributed import sharding as sh
from repro.launch.mesh import make_mesh
from repro.serving import kv_cache as kvc
from repro.serving import weights as swt

# the logical axes the serving placement splits over "model", wherever they
# sit in a leaf (every one maps to the "model" mesh axis)
_TENSOR_PARALLEL = (sh.HEADS, sh.KV_HEADS, sh.FF, sh.VOCAB)


def mesh_shape(rules: sh.ShardingRules) -> Tuple[int, int]:
    """``(data, model)`` sizes of the rules' mesh (``(1, 1)`` when none)."""
    if rules is None or rules.mesh is None:
        return (1, 1)
    sizes = dict(rules.mesh.shape)
    data = 1
    for a in rules.batch_axes:
        data *= sizes.get(a, 1)
    return data, sizes.get(rules.model_axis, 1)


def serving_param_specs(specs):
    """Restrict a logical param-spec tree to the serving placement.

    Keeps HEADS/KV_HEADS/FF/VOCAB wherever they sit (:data:`_TENSOR_PARALLEL`:
    ``wq``/``wk``/``wv``/``gate``/``up`` by column, ``wo``/``down`` by row,
    the embedding and ``lm_head`` by vocabulary) and drops every other
    logical axis (experts, d_model, norms) to replicated.  The result feeds
    :meth:`ShardingRules.tree_shardings`, whose divisibility fallback (with
    a :class:`~repro.distributed.sharding.ShardingFallbackWarning`) still
    applies per leaf.
    """

    def fix(axes):
        return tuple(a if a in _TENSOR_PARALLEL else None for a in axes)

    return jax.tree.map(fix, specs, is_leaf=lambda x: isinstance(x, tuple))


def shard_params(params, specs, rules: sh.ShardingRules):
    """Place params on the rules' mesh under the serving policy.

    ``specs`` is the model zoo's logical tree (``model_zoo.param_specs``).
    """
    return jax.device_put(params, param_shardings(params, specs, rules))


def param_shardings(params, specs, rules: sh.ShardingRules):
    """NamedShardings of the serving policy for ``params`` (arrays or
    ``ShapeDtypeStruct``s) on the rules' mesh."""
    return rules.tree_shardings(
        rules.mesh, serving_param_specs(specs), struct_tree=params,
    )


def projection_shards(params, specs,
                      mesh: Tuple[int, int]) -> Dict[str, Tuple[int, int]]:
    """``{projection: (input-row shards, output-column shards)}`` of the
    decode projections (:func:`repro.serving.weights.projection_paths`) as
    :func:`param_shardings` places ``params`` (arrays or
    ``ShapeDtypeStruct``s) on a ``(data, model)`` mesh of that shape, read
    on an abstract mesh, divisibility fallback included.  A tied head is
    the embedding transposed.  The weight and interconnect accounting
    (``WeightPlan.decode_read_bytes``, ``kv_cache.decode_read_bytes``)
    price from this, so they follow the placement itself."""
    rules = sh.rules_for_mesh(
        AbstractMesh(tuple(int(n) for n in mesh), ("data", "model")))
    placed = param_shardings(params, specs, rules)

    def ways(path):
        shape = tuple(swt.get_path(params, path).shape)
        shard = swt.get_path(placed, path).shard_shape(shape)
        return shape[-2] // shard[-2], shape[-1] // shard[-1]

    out = {proj: ways(path) for path, proj in swt.projection_paths(params)}
    if "lm_head" not in params and "embed" in params:
        rows, cols = ways(("embed",))
        out["lm_head"] = (cols, rows)
    return out


def cache_shardings(cache, cfg, layout, rules: sh.ShardingRules):
    """NamedShardings for a serving cache: KV pools/stacks heads-parallel
    on ``"model"`` (batch over ``"data"`` for slot stacks), page table and
    ``pos`` replicated/host-synced.  ``None`` without a mesh."""
    if rules.mesh is None:
        return None
    return rules.tree_shardings(
        rules.mesh, kvc.cache_specs(cfg, layout), struct_tree=cache
    )


def init_cache(cfg, layout, rules: sh.ShardingRules):
    """A zeroed serving cache (``kv_cache.init_cache_arrays``), made by one
    jitted program straight into its placement on the rules' mesh, so no
    device ever holds the whole cache (a 1x4 deepseek-7b cache of 32
    slots x 4096 int8 positions is 30.9 GiB, 7.7 GiB a device)."""
    make = functools.partial(kvc.init_cache_arrays, cfg, layout)
    if rules is None or rules.mesh is None:
        return make()
    shardings = cache_shardings(jax.eval_shape(make), cfg, layout, rules)
    return jax.jit(make, out_shardings=shardings)()


def shard_cache(cache, cfg, layout, rules: sh.ShardingRules):
    """Place a live cache onto the rules' mesh per ``cache_shardings``
    (identity when the rules carry no mesh)."""
    shardings = cache_shardings(cache, cfg, layout, rules)
    if shardings is None:
        return cache
    return jax.device_put(cache, shardings)


def replicated(x, rules: sh.ShardingRules):
    """Host value → mesh-replicated device array.  Always copies (callers
    hand in live, host-mutated buffers like the allocator's page table)."""
    if rules is None or rules.mesh is None:
        return jnp.asarray(np.asarray(x))
    return jax.device_put(np.asarray(x), NamedSharding(rules.mesh, P()))


def parse_mesh_arg(arg: Optional[str]) -> Tuple[int, int]:
    """``"2,4"`` (or ``"2x4"``) → ``(2, 4)``; ``None``/empty → ``(1, 1)``."""
    if not arg:
        return (1, 1)
    parts = str(arg).replace("x", ",").split(",")
    if len(parts) != 2:
        raise ValueError(f"--mesh expects DATA,MODEL (got {arg!r})")
    d, m = int(parts[0]), int(parts[1])
    if d < 1 or m < 1:
        raise ValueError(f"--mesh sizes must be >= 1 (got {arg!r})")
    return d, m


def rules_for(data: int, model: int) -> sh.ShardingRules:
    """Serving rules over a fresh ``(data, model)`` mesh."""
    return sh.rules_for_mesh(make_mesh(data, model))
