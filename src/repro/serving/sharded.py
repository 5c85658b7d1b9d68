"""Mesh placement for the serving stack — bit-exact by construction.

The serving oracle (tests/test_serving_fuzz.py) pins sharded runs to the
single-device trace *bit-exactly* for bf16 caches.  That rules out the
classic megatron placement wholesale: any weight whose model-mapped logical
axis sits on the *contraction* side of its matmul (``wo``'s heads, the MLP
down-projection's ff) would split a float reduction into a psum of shard
partials, and float addition is not associative.  What remains safe is pure
data movement:

* **output-side (column-parallel) weights** — ``wq``/``wk``/``wv`` carry
  HEADS/KV_HEADS on their *last* axis: each device computes its head slice
  with the full-width d_model contraction, bit-identical to the unsharded
  column.  Likewise ``lm_head``'s vocab columns.
* **gather-side tables** — the embedding's vocab axis: a sharded token
  lookup is a masked gather + an exact ``x + 0`` combine.
* **the attend itself** — with q/k/v and the KV pools sharded on the same
  head axis, every score/softmax/weighted-sum stays device-local per head;
  the per-head outputs are *all-gathered* (concatenated, never summed)
  across ``"model"`` before the replicated ``wo``.

Everything else — ``wo``, the MLP stack, norms — stays replicated.  This
module derives that placement from the model zoo's logical specs
(:func:`serving_param_specs`) and owns the host→device placement of params
and cache (:func:`shard_params` / :func:`shard_cache`) plus the mesh
bookkeeping the scheduler's kv-read accounting reports.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed import sharding as sh
from repro.launch.mesh import make_mesh
from repro.serving import kv_cache as kvc

# logical axes that may shard a weight only when they are the OUTPUT axis
# (last dim) of their matmul — contraction-side occurrences must replicate
_OUTPUT_ONLY = (sh.HEADS, sh.KV_HEADS)
# logical axes that are safe wherever they appear (gather / output side)
_ALWAYS = (sh.VOCAB,)


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
    """Restrict a logical param-spec tree to the bit-exact serving subset.

    Keeps VOCAB anywhere and HEADS/KV_HEADS only on a leaf's last axis
    (column-parallel); every other logical axis is dropped to replicated.
    The result feeds :meth:`ShardingRules.tree_shardings`, whose
    divisibility fallback (with a :class:`~repro.distributed.sharding
    .ShardingFallbackWarning`) still applies per leaf.
    """

    def fix(axes):
        axes = tuple(axes)
        last = len(axes) - 1
        return tuple(
            a if a in _ALWAYS or (a in _OUTPUT_ONLY and i == last) else None
            for i, a in enumerate(axes)
        )

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
        rules.mesh, serving_param_specs(specs), struct_tree=params
    )


def cache_shardings(cache, cfg, layout, rules: sh.ShardingRules):
    """NamedShardings for a serving cache: KV pools/stacks heads-parallel
    on ``"model"`` (batch over ``"data"`` for slot stacks), page table and
    ``pos`` replicated/host-synced.  ``None`` without a mesh."""
    if rules.mesh is None:
        return None
    return rules.tree_shardings(
        rules.mesh, kvc.cache_specs(cfg, layout), struct_tree=cache
    )


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
