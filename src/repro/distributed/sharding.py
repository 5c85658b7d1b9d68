"""Logical-axis sharding rules → NamedSharding/PartitionSpec.

Every parameter leaf in the model zoo is annotated with a tuple of *logical*
axis names (see ``models/*.py: param_specs``).  This module maps them onto
the physical mesh axes:

  single pod : mesh ("data", "model") = (16, 16)
  multi-pod  : mesh ("pod", "data", "model") = (2, 16, 16)

Default rules are megatron-style tensor parallelism over "model" and batch
parallelism over "data" (+"pod").  Strategy knobs:

  fsdp_axes  — logical axes additionally sharded over "data" (ZeRO-3 style
               per-layer all-gather; required to fit jamba-398B),
  seq_shard  — shard the KV-cache sequence axis over "data" for the
               long_500k batch=1 cells (the distattention pattern).
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax._src import mesh as _mesh_lib

# logical axis vocabulary used by the model zoo
BATCH = "batch"
SEQ = "seq"  # activation sequence axis (sequence parallelism / long-ctx KV)
TOKENS = "tokens"  # flattened B*S: all axes that shard tokens (MoE groups)
VOCAB = "vocab"
D_MODEL = "d_model"
HEADS = "heads"
KV_HEADS = "kv_heads"
HEAD_DIM = "head_dim"
FF = "ff"
EXPERT = "expert"
LAYERS = "layers"  # stacked-scan leading dim: never sharded
CONV = "conv"
STATE = "state"
VISION = "vision"
NONE = None


class ShardingFallbackWarning(UserWarning):
    """A logical axis could not shard its dim and was silently replicated.

    Raised (as a warning, not an error) by :meth:`ShardingRules
    .spec_for_shape` so a mis-sized tensor — e.g. a KV pool whose head axis
    does not divide the ``"model"`` mesh axis — shows up in logs instead of
    masquerading as a correctly sharded one.  Divisibility fallback remains
    the *behaviour*; the warning only adds the missing signal."""


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Maps logical axis name -> mesh axis (or None = replicated)."""

    batch_axes: Tuple[str, ...] = ("data",)  # ("pod","data") on multi-pod
    model_axis: str = "model"
    # logical -> mesh; anything absent is replicated
    fsdp_axes: Tuple[str, ...] = ()  # logical axes to also shard over data
    seq_shard: bool = False  # shard KV seq over data (long-context decode)
    sp: bool = False  # sequence parallelism: activations' seq over model
    # concrete mesh for in-graph constraints ("with mesh:" alone does NOT
    # make PartitionSpec constraints resolvable inside jit)
    mesh: Optional[Mesh] = None

    def mesh_axis(self, logical: Optional[str]):
        if logical is None:
            return None
        if logical == BATCH:
            if self.seq_shard:
                return None  # long-context decode: data axis belongs to SEQ
            ax = tuple(self.batch_axes)
            return ax if len(ax) > 1 else ax[0]
        if logical in (VOCAB, HEADS, KV_HEADS, FF, EXPERT):
            return self.model_axis
        if logical == SEQ:
            if self.seq_shard:
                return tuple(self.batch_axes)
            return self.model_axis if self.sp else None
        if logical == TOKENS:
            # token groups shard over the batch axes ONLY: the "model" axis
            # belongs to the TP-sharded expert FF dim, and claiming it here
            # forces the partitioner to replicate expert compute (§Perf B3)
            ax = tuple(self.batch_axes)
            return ax if len(ax) > 1 else (ax[0] if ax else None)
        if logical in self.fsdp_axes:
            # ZeRO-3: weight's d_model (or ff) axis sharded over data too
            return tuple(self.batch_axes)
        return None

    def token_groups(self, n_tokens: int) -> int:
        """Number of shard-aligned groups the flattened token dim splits
        into (MoE group-local dispatch).  1 when no mesh is attached."""
        import math as _math

        if self.mesh is None:
            return 1
        sizes = dict(self.mesh.shape)
        g = 1
        for a in self.batch_axes:
            g *= sizes.get(a, 1)
        if self.sp:
            g *= sizes.get(self.model_axis, 1)
        return _math.gcd(n_tokens, g)

    def group_sizes(self, batch: int, seq: int):
        """(Gb, Gs): shard-aligned group factors along batch and seq.

        A single flatten of (B, S) across two sharded mesh axes is NOT
        expressible in GSPMD (reshape would split within shards); factoring
        per-dim keeps every reshape aligned with exactly one axis
        (§Perf iteration B3).
        """
        import math as _math

        if self.mesh is None:
            return 1, 1
        sizes = dict(self.mesh.shape)
        gb = 1
        for a in self.batch_axes:
            gb *= sizes.get(a, 1)
        gb = _math.gcd(batch, gb)
        # Gs stays 1: the MoE block is a sequence-parallel REGION BOUNDARY
        # (megatron-SP style) — S is all-gathered entering the expert FFN so
        # token groups never claim the model axis (§Perf B3/B4).
        return gb, 1

    def spec(self, logical_axes: Tuple[Optional[str], ...]) -> P:
        used = set()
        out = []
        for ax in logical_axes:
            phys = self.mesh_axis(ax)
            # a mesh axis may appear at most once in a PartitionSpec
            key = tuple(phys) if isinstance(phys, tuple) else (phys,)
            if phys is None or any(k in used for k in key if k is not None):
                out.append(None)
            else:
                used.update(k for k in key if k is not None)
                out.append(phys)
        return P(*out)

    def spec_for_shape(self, mesh: Mesh, logical_axes, shape) -> P:
        """Like :meth:`spec` but duplicate-axis and divisibility handling are
        joint: an axis that can't shard a dim (kv_heads=8 on model=16) stays
        AVAILABLE for a later logical dim (e.g. the KV sequence) — this is
        what turns few-head decode caches into flash-decode seq sharding
        instead of replication."""
        sizes = dict(mesh.shape)  # works for Mesh and AbstractMesh
        used = set()
        out = []
        for dim, logical in zip(shape, tuple(logical_axes) + (None,) * len(shape)):
            phys = self.mesh_axis(logical)
            if phys is None:
                out.append(None)
                continue
            axes = phys if isinstance(phys, tuple) else (phys,)
            total = 1
            for a in axes:
                total *= sizes[a]
            if any(a in used for a in axes):
                out.append(None)
                continue
            if dim % total != 0:
                # dim == 1 is "nothing to shard" (B=1 chunk prefill, squeezed
                # axes) — only a real size mismatch warrants the signal
                if dim > 1:
                    warnings.warn(
                        f"logical axis {logical!r} (dim {dim}) is not "
                        f"divisible by mesh axes {tuple(axes)} (size {total})"
                        "; replicating instead",
                        ShardingFallbackWarning,
                        stacklevel=2,
                    )
                out.append(None)
                continue
            used.update(axes)
            out.append(phys)
        return P(*out)

    def tree_specs(self, logical_tree) -> jax.tree_util.PyTreeDef:
        """Map a pytree of logical-axis tuples to PartitionSpecs."""
        return jax.tree.map(
            lambda axes: self.spec(tuple(axes)),
            logical_tree,
            is_leaf=lambda x: isinstance(x, tuple),
        )

    def tree_shardings(self, mesh: Mesh, logical_tree, struct_tree=None):
        """NamedShardings for a spec tree; with ``struct_tree`` (matching
        pytree of shaped values) the specs become divisibility-safe."""
        is_leaf = lambda x: isinstance(x, tuple)
        if struct_tree is None:
            return jax.tree.map(
                lambda axes: NamedSharding(mesh, self.spec(tuple(axes))),
                logical_tree,
                is_leaf=is_leaf,
            )
        flat_specs, treedef = jax.tree_util.tree_flatten(logical_tree, is_leaf=is_leaf)
        flat_structs = treedef.flatten_up_to(struct_tree)
        out = [
            NamedSharding(mesh, self.spec_for_shape(mesh, ax, s.shape))
            for ax, s in zip(flat_specs, flat_structs)
        ]
        return jax.tree_util.tree_unflatten(treedef, out)


def rules_for_mesh(mesh: Mesh, **kw) -> ShardingRules:
    """Default rules for a production mesh (adds 'pod' to batch axes)."""
    axes = mesh.axis_names
    batch = tuple(a for a in ("pod", "data") if a in axes)
    return ShardingRules(batch_axes=batch or ("data",), mesh=mesh, **kw)


def ambient_mesh():
    """The ambient mesh, or None outside every mesh context.

    ``jax.sharding.use_mesh`` sets an abstract mesh (bare PartitionSpec
    constraints resolve against it); the plain ``with mesh:`` form the
    launchers use sets only the concrete mesh in JAX's thread resources.
    An axis-less abstract mesh counts as "none".
    """
    am = jax.sharding.get_abstract_mesh()
    if am.axis_names:
        return am
    pm = _mesh_lib.thread_resources.env.physical_mesh
    return None if pm.empty else pm


def constrain(x: jax.Array, rules: ShardingRules, logical_axes) -> jax.Array:
    """with_sharding_constraint via logical axes (no-op outside jit/mesh).

    Divisibility-safe: axes that don't divide the corresponding dim are
    dropped (few-head archs like gemma3-1b replicate heads instead of
    forcing an invalid 16-way split).  Uses the rules' concrete mesh when
    present (a plain ``with mesh:`` does not make PartitionSpec constraints
    resolvable inside jit); falls back to the ambient abstract mesh.
    """
    try:
        mesh = rules.mesh if rules.mesh is not None else ambient_mesh()
        if mesh is None or not mesh.axis_names:
            return x
        spec = rules.spec_for_shape(mesh, tuple(logical_axes), x.shape)
        if isinstance(mesh, Mesh):
            # concrete mesh (rules-attached, or the ambient ``with mesh:``
            # form): bare PartitionSpec constraints don't resolve inside
            # jit there, so wrap in a NamedSharding
            return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        return x
