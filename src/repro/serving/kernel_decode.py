"""Route the serving engine's global-layer decode attend onto the Pallas
paged-attention kernel families.

The engine's decode hot path (``engine._attn_decode_layer``, global
branch) has two kernel-backed formats:

* ``paged_flash_decode`` — bf16 / int8 dense attend.  It takes the WHOLE
  stacked cache and the layer index, and reads the layer in place: no
  step program copies one layer out of the stack for the custom call.
  The paged layout passes its ``(L, n_tok, Hk*D)`` pools and the
  ``(B, S_max/page_size)`` page table straight in (the BlockSpec index
  map gathers physical pages; ``kv_cache.paged_entry``'s contiguous
  per-slot view is never built).  The slot layout's token-row
  ``(L, B, S, Hk*D)`` stacks are the same rows with a batch dim: their
  leading dims merge into ``(L, B*S, Hk*D)`` for free, and an identity
  page table addresses each slot as ``S/P`` sub-pages of ``P`` rows, ``P``
  from the shapes (``slot_page_rows``), so one kernel serves both.  The
  slot stacks' int8 scales ``(L, B, Hk, S)`` are read in place; the paged
  pools' token-major scales are gathered per slot for the one layer.
* ``bgpp_paged_attend`` — the fused two-phase BGPP decode (plane scan,
  progressive top-k, exact int8 attend over the survivors) in one launch.
  It still takes one layer's token-major pool leaves, so a slot-layout
  bgpp store is transposed into them first (``_pool_views``); no
  benchmark cell runs it.

Mode resolution happens ONCE at ``make_serve_step`` build time
(:func:`resolve`): the ``decode_kernel`` config knob (or the
``REPRO_DECODE_KERNEL`` env var) picks ``jnp`` (legacy engine paths,
bit-for-bit the pre-kernel behavior), ``interpret`` (Pallas interpret —
the CPU CI parity mode), ``kernel`` (compiled Mosaic), or ``auto``
(kernel on TPU backends, jnp elsewhere).

Sharding: with a mesh attached and a non-trivial model axis the attend is
wrapped in ``shard_map`` exactly like the engine's
``_bgpp_paged_decode_attend_sharded`` — each device runs the kernel on
its own (batch, head) shard of the pool, no collective introduced.  When
the head counts don't divide the model axis, :func:`decode_attend`
returns ``None`` and the engine falls back to its jnp path (the same
divisibility fallback the cache placement applies).
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.distributed import sharding as sh
from repro.kernels import MODE_COMPILED, MODE_INTERPRET
from repro.kernels.bgpp_paged_attend import bgpp_paged_attend
from repro.kernels.paged_flash_decode import paged_flash_decode
from repro.kernels.paged_flash_decode.kernel import (
    slot_page_rows, slot_table,
)
from repro.serving import kv_cache as kvc

Tree = Dict[str, Any]

ENV_VAR = "REPRO_DECODE_KERNEL"
MODES = ("auto", "jnp", "interpret", "kernel")
# internal execution modes after resolution
_EXEC = {"jnp": "jnp", "interpret": MODE_INTERPRET, "kernel": MODE_COMPILED}


def resolve(cfg) -> str:
    """Resolve the ``decode_kernel`` knob to an execution mode.

    Returns ``"jnp"`` (legacy engine attend), ``"interpret"`` or
    ``"compiled"`` (kernel dispatch modes).  ``REPRO_DECODE_KERNEL``
    overrides the config so CI matrices can flip the path without
    touching configs; ``auto`` picks the compiled kernel on TPU backends
    and the jnp path everywhere else (CPU default behavior is therefore
    bit-for-bit unchanged).
    """
    knob = os.environ.get(ENV_VAR, "").strip() or getattr(
        cfg.mcbp, "decode_kernel", "auto"
    )
    if knob not in MODES:
        raise ValueError(
            f"decode_kernel={knob!r} is not one of {MODES} (config "
            f"mcbp.decode_kernel or ${ENV_VAR})"
        )
    if knob == "auto":
        knob = "kernel" if jax.default_backend() == "tpu" else "jnp"
    return _EXEC[knob]


def validate(cfg, layout) -> None:
    """Raise actionable errors for configs the kernel path cannot serve.

    Called once at ``make_serve_step`` build time when the resolved mode
    is not ``jnp`` — shape/divisibility mistakes surface here with a
    config-level message instead of failing inside Pallas lowering.
    """
    if cfg.num_heads % cfg.num_kv_heads:
        raise ValueError(
            f"decode_kernel: num_heads={cfg.num_heads} is not a multiple of "
            f"num_kv_heads={cfg.num_kv_heads} — the GQA group size must be "
            f"integral for the grouped (B, Hk, g, Dh) kernel query layout"
        )
    # NOTE: max_seq need not be page-aligned — both kernels stage the full
    # page-covered span (pages_per_slot * page_size lanes) and mask lanes
    # past pos (bgpp: past the plan's context too) exactly like the engine.
    if layout.kv_format == "bgpp":
        if cfg.head_dim % 8:
            raise ValueError(
                f"decode_kernel: head_dim={cfg.head_dim} is not a multiple "
                f"of 8 — bgpp packs bit planes bytewise"
            )
        rounds, k_max, survivors = kvc.bgpp_decode_plan(layout.max_seq, cfg)
        if survivors[0] != layout.max_seq or k_max > layout.max_seq:
            raise ValueError(
                f"decode_kernel: bgpp plan (rounds={rounds}, k_max={k_max}, "
                f"survivors={survivors}) is inconsistent with "
                f"max_seq={layout.max_seq} — check bgpp_rounds / "
                f"bgpp_keep_ratio"
            )


def _pool_views(store: Tree, gi: int, slot_layout: bool) -> Tree:
    """Layer ``gi`` of a bgpp store as token-major pool leaves.

    Paged stores already hold token-major pools — this just indexes the
    layer.  Slot stores become ``(B*S, Hk, ...)`` pools whose row
    ``b*S + s`` is slot ``b``'s logical position ``s`` (an identity page
    table of one ``S``-row page per slot addresses them): their leaves are
    heads-major, so each is transposed.  Only ``bgpp_paged_attend`` reads
    these copies; ``paged_flash_decode`` reads the stacked cache in place.
    """
    if not slot_layout:
        return {n: store[n][gi] for n in
                ("k_planes", "k_sign", "k_scale", "v", "v_scale")}
    out: Tree = {}
    for n in store:
        a = store[n][gi]
        if n == "k_planes":  # (NBITS, B, Hk, S, D/8) -> (NBITS, B*S, Hk, D/8)
            nb, B, Hk, S, Dp = a.shape
            out[n] = a.transpose(0, 1, 3, 2, 4).reshape(nb, B * S, Hk, Dp)
        elif a.ndim == 4:  # (B, Hk, S, D) -> (B*S, Hk, D)
            B, Hk, S, D = a.shape
            out[n] = a.transpose(0, 2, 1, 3).reshape(B * S, Hk, D)
        else:  # scales (B, Hk, S) -> (B*S, Hk)
            B, Hk, S = a.shape
            out[n] = a.transpose(0, 2, 1).reshape(B * S, Hk)
    return out


def _attend_local(q1, store: Tree, gi: int, pos, table, cfg, layout,
                  mode: str):
    """Run the kernel family on device-local operands -> ``(B, Hq, Dh)``.

    ``store`` is the device's shard of ``cache["global"]``.  ``table`` is
    the page table — for the slot layout the caller passes ``None`` and an
    identity table over the stack's ``(B*S)`` rows is built here from the
    LOCAL batch size, so the same body serves the ``shard_map``-wrapped and
    unsharded calls.
    """
    B, Hq, Dh = q1.shape
    g = cfg.num_heads // cfg.num_kv_heads  # ratio: shard-invariant
    Hk = Hq // g
    qg = q1.reshape(B, Hk, g, Dh).astype(jnp.float32)
    fmt = layout.kv_format
    S = layout.max_seq

    if fmt == "bgpp":
        pool = _pool_views(store, gi, table is None)
        page_size = layout.page_size
        if table is None:  # slot: slot b's rows [b*S, (b+1)*S) are its page
            table = jnp.arange(B, dtype=jnp.int32)[:, None]
            page_size = S
        rounds, k_max, survivors = kvc.bgpp_decode_plan(S, cfg)
        out = bgpp_paged_attend(
            qg, pool["k_planes"], pool["k_sign"], pool["k_scale"],
            pool["v"], pool["v_scale"], table, pos, page_size=page_size,
            rounds=rounds, k_max=k_max, survivors=survivors, mode=mode,
        )
    else:
        k, v = store["k"], store["v"]
        page_size = layout.page_size
        if table is None:  # slot: (L, B, S, Hk*D) -> (L, B*S, Hk*D), free
            L, _, _, width = k.shape
            page_size = slot_page_rows(S, width, k.dtype.itemsize)
            k = k.reshape(L, B * S, width)
            v = v.reshape(L, B * S, width)
            table = slot_table(B, S, page_size)
        # int8 scales as stored: the slot stacks' (L, B, Hk, S) rows are
        # read in place, the paged pools' (L, n_tok, Hk) gathered per slot
        scales = (
            {} if fmt == "bf16"
            else {"k_scale": store["k_scale"], "v_scale": store["v_scale"]}
        )
        out = paged_flash_decode(
            qg, k, v, table, pos, gi,
            page_size=page_size, mode=mode, **scales,
        )
    # (B, Hk, g, Dh) -> (B, Hq, Dh): same axis order as the engine's
    # transpose/reshape epilogue
    return out.reshape(B, Hq, Dh)


def decode_attend(q1, store: Tree, gi: int, pos, cfg, layout, rules,
                  mode: str, page_table=None):
    """Kernel-backed global-layer decode attend, or ``None`` to fall back.

    q1 ``(B, Hq, Dh)``; ``store`` is ``cache["global"]``; ``pos`` the
    per-slot positions ``(B,)``.  Paged layouts pass ``phys`` (bgpp) and
    ``page_table`` (dense formats); the slot layout passes neither.
    Returns f32 ``(B, Hq, Dh)`` matching the engine's jnp attend, or
    ``None`` when ``mode == "jnp"`` or the mesh's model axis doesn't
    divide the head counts (the engine then runs its legacy path).
    """
    if mode == "jnp":
        return None
    slot = layout.layout != "paged"
    table = None if slot else page_table
    pos = pos.astype(jnp.int32)

    mesh = getattr(rules, "mesh", None)
    m = dict(mesh.shape).get(rules.model_axis, 1) if mesh is not None else 1
    if mesh is None or m <= 1:
        return _attend_local(q1, store, gi, pos, table, cfg, layout, mode)
    if cfg.num_kv_heads % m or cfg.num_heads % m:
        return None  # heads don't shard: engine jnp fallback (replicated)
    if slot and (getattr(rules, "seq_shard", False) or getattr(rules, "sp", False)):
        return None  # seq-sharded slot stacks break the identity pool maps
    def run(q_, store_, pos_, table_):
        t = table_ if not slot else None
        return _attend_local(q_, store_, gi, pos_, t, cfg, layout, mode)

    spec = lambda axes, x: rules.spec_for_shape(mesh, axes, x.shape)
    store_spec = jax.tree.map(
        lambda axes, x: spec(tuple(axes), x),
        kvc.cache_specs(cfg, layout)["global"], store,
        is_leaf=lambda x: isinstance(x, tuple),
    )
    if table is None:  # slot layout: feed a dummy all-devices scalar map
        table = jnp.zeros((q1.shape[0], 1), jnp.int32)
    return jax.shard_map(
        run, mesh=mesh,
        in_specs=(
            spec((sh.BATCH, sh.HEADS, None), q1),
            store_spec,
            spec((sh.BATCH,), pos),
            spec((sh.BATCH, None), table),
        ),
        out_specs=spec((sh.BATCH, sh.HEADS, None), q1),
        check_vma=False,
    )(q1, store, pos, table)
