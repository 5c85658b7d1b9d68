"""KV-cache containers for the serving engine.

Three storage formats (MCBPOptions.weight_format governs weights; the cache
format here is chosen by ``kv_format``):

  bf16 — dense baseline.
  int8 — per-token/head symmetric INT8 K and V (+f32 scales) — the paper's
         Atom-style 8-bit KV baseline; halves the decode memory term.
  bgpp — K magnitudes stored as packed bit-planes (+ sign plane, + scale)
         so the BGPP predictor fetches one plane per round; V stays int8.

Mixed local/global attention stacks (gemma3, mixtral SWA, llama4 chunked)
keep two stacks: local layers get a ring buffer of ``window`` slots, global
layers the full sequence — this is what makes gemma3/llama4 ``long_500k``
memory-feasible.  Logical-axis specs accompany every array so the dry-run
can shard caches ((pod,)data over batch, or sequence for long_500k).

Two physical layouts for the *global* stacks (``CacheLayout.layout``):

  slot  — one dense ``(B, S_max)`` row per batch slot (the default; every
          oracle baseline).
  paged — vLLM-style pools: each layer stores ``num_pages * page_size``
          token rows with no batch dim, and a per-slot page table
          ``(B, S_max // page_size)`` of physical page ids maps logical
          positions to pool rows.  Writes translate logical → physical with
          the same OOB-scatter-drop convention (unmapped page or padded
          lane => dropped); reads gather the slot's logical row back into
          the heads-major view the jnp attends read of a slot stack
          (:func:`slot_entry`), so attention consumes bit-identical values
          (verified by the serving fuzz oracle).  The slot stacks' bf16/int8
          K/V bodies share the pools' token rows ``(..., Hk*D)`` (a slot
          row is one ``S``-row page).  Page lifecycle (free lists, refcounts,
          prefix reuse) is host-side: :mod:`repro.serving.paging`.  Local
          ring stacks, mamba state, and cross memory stay slot-major —
          rings are already fixed-width per-slot pages by construction.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import bitslice
from repro.distributed import sharding as sh

Tree = Dict[str, Any]

NBITS = bitslice.WEIGHT_MAG_BITS  # 7 magnitude planes + sign

# scatter target for padded chunk lanes: far out of every seq axis, so JAX's
# drop-out-of-bounds scatter semantics discard the write (never clamps)
OOB_INDEX = 1 << 30


def _dt(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


@dataclasses.dataclass(frozen=True)
class CacheLayout:
    """Static description of one model's decode cache."""

    arch: str
    family: str
    batch: int
    max_seq: int
    kv_format: str  # bf16 | int8 | bgpp
    global_layers: Tuple[int, ...] = ()
    local_layers: Tuple[int, ...] = ()
    local_window: int = 0
    mamba_layers: Tuple[int, ...] = ()
    has_cross: bool = False  # whisper encoder memory
    layout: str = "slot"  # slot | paged (global stacks only)
    page_size: int = 0  # tokens per page (paged layout)
    num_pages: int = 0  # physical pages in each layer's pool (paged layout)

    @property
    def pages_per_slot(self) -> int:
        """Logical pages needed to map one slot's full ``max_seq`` row."""
        return -(-self.max_seq // self.page_size) if self.page_size else 0


def layout_for(cfg, batch: int, max_seq: int, kv_format: str = "int8",
               layout: str = "slot", page_size: int = 8,
               num_pages: Optional[int] = None) -> CacheLayout:
    """Derive a :class:`CacheLayout` from a model config: classify every
    layer as global (full-sequence stack), local (ring buffer of the
    sliding/chunked window), or mamba state, and — for
    ``layout="paged"`` — size the shared page pool (default capacity
    equals the dense allocation, so admission can never exhaust it;
    pass a smaller ``num_pages`` to oversubscribe via prefix sharing)."""
    glob, loc, mamba = [], [], []
    window = 0
    for i in range(cfg.num_layers):
        if not cfg.layer_is_attention(i):
            mamba.append(i)
            continue
        kind, w = cfg.layer_attn_window(i)
        if kind in ("sliding",) and w > 0:
            loc.append(i)
            window = w
        elif kind == "chunked" and w > 0:
            # chunked attention never needs more than the chunk in cache
            loc.append(i)
            window = w
        else:
            glob.append(i)
    assert layout in ("slot", "paged"), layout
    if layout == "paged":
        assert page_size >= 1, "paged layout needs page_size >= 1"
        pages_per_slot = -(-max_seq // page_size)
        # default capacity == the dense allocation, so admission can never
        # exhaust the pool; smaller pools oversubscribe (prefix sharing)
        num_pages = batch * pages_per_slot if num_pages is None else num_pages
    else:
        page_size, num_pages = 0, 0
    return CacheLayout(
        arch=cfg.name,
        family=cfg.family,
        batch=batch,
        max_seq=max_seq,
        kv_format=kv_format,
        global_layers=tuple(glob),
        local_layers=tuple(loc),
        local_window=min(window, max_seq) if window else 0,
        mamba_layers=tuple(mamba),
        has_cross=cfg.family == "enc_dec",
        layout=layout,
        page_size=page_size,
        num_pages=num_pages,
    )


# --------------------------------------------------------------------------
# allocation
# --------------------------------------------------------------------------


def _kv_stack(n_layers, B, S, Hk, Dh, kv_format, dtype):
    # bf16/int8 k/v bodies are token rows (B, S, Hk*D), the paged pools'
    # row order: the step programs scatter one decode token's row in place
    # instead of relaying the whole stack around the write, and a head's
    # rows are a lane block [h*D, (h+1)*D) the decode kernel DMAs as
    # stored.  Scales stay lane-dense (B, Hk, S) rows; the bgpp store stays
    # heads-major throughout (see token_major)
    p: Tree = {}
    if n_layers == 0:
        return p
    if kv_format == "bf16":
        p["k"] = jnp.zeros((n_layers, B, S, Hk * Dh), dtype)
        p["v"] = jnp.zeros((n_layers, B, S, Hk * Dh), dtype)
    elif kv_format == "int8":
        for n in ("k", "v"):
            p[n] = jnp.zeros((n_layers, B, S, Hk * Dh), jnp.int8)
            p[f"{n}_scale"] = jnp.zeros((n_layers, B, Hk, S), jnp.float32)
    elif kv_format == "bgpp":
        assert Dh % 8 == 0
        p["k_planes"] = jnp.zeros((n_layers, NBITS, B, Hk, S, Dh // 8), jnp.uint8)
        p["k_sign"] = jnp.zeros((n_layers, B, Hk, S, Dh // 8), jnp.uint8)
        p["k_scale"] = jnp.zeros((n_layers, B, Hk, S), jnp.float32)
        p["v"] = jnp.zeros((n_layers, B, Hk, S, Dh), jnp.int8)
        p["v_scale"] = jnp.zeros((n_layers, B, Hk, S), jnp.float32)
    else:
        raise ValueError(kv_format)
    return p


def _kv_pool(n_layers, n_tok, Hk, Dh, kv_format, dtype):
    """Paged pool: token-major ``(L, n_tok, ...)`` per-layer stores with NO
    batch dim — ``n_tok = num_pages * page_size`` physical rows shared by
    every slot through the page table; bf16/int8 K/V rows are ``Hk*D``
    wide, as in the slot stacks.  Token-major lets page gathers/scatters
    address one contiguous row axis; reads restore the heads-major view
    (:func:`paged_entry`)."""
    p: Tree = {}
    if n_layers == 0:
        return p
    if kv_format == "bf16":
        p["k"] = jnp.zeros((n_layers, n_tok, Hk * Dh), dtype)
        p["v"] = jnp.zeros((n_layers, n_tok, Hk * Dh), dtype)
    elif kv_format == "int8":
        for n in ("k", "v"):
            p[n] = jnp.zeros((n_layers, n_tok, Hk * Dh), jnp.int8)
            p[f"{n}_scale"] = jnp.zeros((n_layers, n_tok, Hk), jnp.float32)
    elif kv_format == "bgpp":
        assert Dh % 8 == 0
        p["k_planes"] = jnp.zeros((n_layers, NBITS, n_tok, Hk, Dh // 8), jnp.uint8)
        p["k_sign"] = jnp.zeros((n_layers, n_tok, Hk, Dh // 8), jnp.uint8)
        p["k_scale"] = jnp.zeros((n_layers, n_tok, Hk), jnp.float32)
        p["v"] = jnp.zeros((n_layers, n_tok, Hk, Dh), jnp.int8)
        p["v_scale"] = jnp.zeros((n_layers, n_tok, Hk), jnp.float32)
    else:
        raise ValueError(kv_format)
    return p


def _kv_pool_specs(kv_format):
    # pool token rows are randomly assigned to slots, so neither BATCH nor
    # SEQ sharding applies to the token axis; heads-shard only.  The page
    # table is host-owned (the allocator mutates it every admission) and
    # stays replicated: every device needs every slot's logical→physical
    # map to gather its own head shard of any row.
    if kv_format == "bf16":
        ax = (sh.LAYERS, None, sh.KV_HEADS)
        return {"k": ax, "v": ax}
    if kv_format == "int8":
        s = {}
        for n in ("k", "v"):
            s[n] = (sh.LAYERS, None, sh.KV_HEADS)
            s[f"{n}_scale"] = (sh.LAYERS, None, sh.KV_HEADS)
        return s
    if kv_format == "bgpp":
        return {
            "k_planes": (sh.LAYERS, None, None, sh.KV_HEADS, None),
            "k_sign": (sh.LAYERS, None, sh.KV_HEADS, None),
            "k_scale": (sh.LAYERS, None, sh.KV_HEADS),
            "v": (sh.LAYERS, None, sh.KV_HEADS, None),
            "v_scale": (sh.LAYERS, None, sh.KV_HEADS),
        }
    raise ValueError(kv_format)


def _kv_stack_specs(kv_format):
    # bf16/int8 token rows (..., Hk*D) shard over the model axis as their
    # heads do: a device holds whole heads whenever Hk divides the axis
    # (the decode kernel's shard_map needs that; where it does not, the
    # engine takes its jnp attend)
    if kv_format == "bf16":
        ax = (sh.LAYERS, sh.BATCH, sh.SEQ, sh.KV_HEADS)
        return {"k": ax, "v": ax}
    if kv_format == "int8":
        s = {}
        for n in ("k", "v"):
            s[n] = (sh.LAYERS, sh.BATCH, sh.SEQ, sh.KV_HEADS)
            s[f"{n}_scale"] = (sh.LAYERS, sh.BATCH, sh.KV_HEADS, sh.SEQ)
        return s
    if kv_format == "bgpp":
        # NOTE: no SEQ sharding — the progressive top-k uses global indices,
        # and gathers across a sharded seq dim degenerate into per-round
        # all-gathers of the whole plane arrays.  The scalable design is
        # shard-local top-k + a small merge collective (distattention-style),
        # which belongs to the Pallas kernel path (DESIGN.md §2); the jnp
        # dry-run variant shards batch/heads only.
        return {
            "k_planes": (sh.LAYERS, None, sh.BATCH, sh.KV_HEADS, None, None),
            "k_sign": (sh.LAYERS, sh.BATCH, sh.KV_HEADS, None, None),
            "k_scale": (sh.LAYERS, sh.BATCH, sh.KV_HEADS, None),
            "v": (sh.LAYERS, sh.BATCH, sh.KV_HEADS, None, None),
            "v_scale": (sh.LAYERS, sh.BATCH, sh.KV_HEADS, None),
        }
    raise ValueError(kv_format)


def cache_specs(cfg, layout: CacheLayout) -> Tree:
    """Logical-axis specs for the cache — pure (no allocation, dry-run path)."""
    specs: Tree = {"pos": (sh.BATCH,)}
    if layout.global_layers:
        if layout.layout == "paged":
            specs["global"] = _kv_pool_specs(layout.kv_format)
            specs["page_table"] = (None, None)
        else:
            specs["global"] = _kv_stack_specs(layout.kv_format)
    if layout.local_layers:
        fmt = "int8" if layout.kv_format == "bgpp" else layout.kv_format
        s = _kv_stack_specs(fmt)
        s["abs_pos"] = (sh.LAYERS, sh.BATCH, None)
        specs["local"] = s
    if layout.mamba_layers:
        specs["mamba"] = {
            "h": (sh.LAYERS, sh.BATCH, sh.FF, None, None),
            "conv": (sh.LAYERS, sh.BATCH, None, sh.FF),
        }
    if layout.has_cross:
        specs["cross_k"] = (sh.LAYERS, sh.BATCH, sh.KV_HEADS, None, None)
        specs["cross_v"] = (sh.LAYERS, sh.BATCH, sh.KV_HEADS, None, None)
    return specs


def init_cache_arrays(cfg, layout: CacheLayout) -> Tree:
    """Cache pytree (zeros).  Safe under jax.eval_shape for the dry-run."""
    B, S = layout.batch, layout.max_seq
    dtype = _dt(cfg.dtype)
    # per-slot decode positions: slot b of the batch holds its own sequence,
    # so requests of different lengths can coexist (continuous batching)
    cache: Tree = {"pos": jnp.zeros((B,), jnp.int32)}
    if layout.global_layers:
        if layout.layout == "paged":
            cache["global"] = _kv_pool(
                len(layout.global_layers), layout.num_pages * layout.page_size,
                cfg.num_kv_heads, cfg.head_dim, layout.kv_format, dtype,
            )
            # -1 == unmapped: writes through the table drop, reads clamp to
            # row 0 and rely on the caller's position masks
            cache["page_table"] = jnp.full(
                (B, layout.pages_per_slot), -1, jnp.int32
            )
        else:
            cache["global"] = _kv_stack(
                len(layout.global_layers), B, S, cfg.num_kv_heads, cfg.head_dim,
                layout.kv_format, dtype,
            )
    if layout.local_layers:
        # local ring buffers stay dense (int8): windows are small, and BGPP
        # targets the big global/full caches (paper's long-context case)
        fmt = "int8" if layout.kv_format == "bgpp" else layout.kv_format
        p = _kv_stack(
            len(layout.local_layers), B, layout.local_window,
            cfg.num_kv_heads, cfg.head_dim, fmt, dtype,
        )
        # ring buffers hold absolute positions for RoPE-correct reuse
        p["abs_pos"] = jnp.full(
            (len(layout.local_layers), B, layout.local_window), -1, jnp.int32
        )
        cache["local"] = p
    if layout.mamba_layers:
        d_in = cfg.ssm_expand * cfg.d_model
        nheads = d_in // cfg.ssm_head_dim
        cache["mamba"] = {
            "h": jnp.zeros(
                (len(layout.mamba_layers), B, nheads, cfg.ssm_head_dim, cfg.ssm_state),
                jnp.float32,
            ),
            "conv": jnp.zeros(
                (len(layout.mamba_layers), B, cfg.ssm_conv - 1,
                 d_in + 2 * cfg.ssm_state),
                dtype,
            ),
        }
    if layout.has_cross:
        cache["cross_k"] = jnp.zeros(
            (cfg.num_layers, B, cfg.num_kv_heads, cfg.encoder_seq, cfg.head_dim),
            dtype,
        )
        cache["cross_v"] = jnp.zeros_like(cache["cross_k"])
    return cache


def init_cache(cfg, layout: CacheLayout) -> Tuple[Tree, Tree]:
    """Returns (cache pytree, logical-axis specs)."""
    return init_cache_arrays(cfg, layout), cache_specs(cfg, layout)


def constrain_cache(cache: Tree, specs: Tree, rules) -> Tree:
    """Pin every cache leaf to its logical-axis sharding inside a jitted
    step.  A no-op when ``rules`` carries no mesh, so single-device paths
    compile identical programs.  Applied at the end of serve_step / chunk
    so scatter-updated pools keep their heads-parallel placement and donated
    buffers are reused in place instead of resharded."""
    if getattr(rules, "mesh", None) is None:
        return cache
    is_leaf = lambda x: isinstance(x, tuple)
    flat_specs, treedef = jax.tree_util.tree_flatten(specs, is_leaf=is_leaf)
    flat = treedef.flatten_up_to(cache)
    out = [sh.constrain(a, rules, ax) for ax, a in zip(flat_specs, flat)]
    return jax.tree_util.tree_unflatten(treedef, out)


def cache_bytes(cache: Tree) -> int:
    """Total bytes resident across every leaf of a cache pytree."""
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))


# --------------------------------------------------------------------------
# quantized read/write helpers
# --------------------------------------------------------------------------


def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-vector int8 quantization over the trailing head dim.

    Rank-polymorphic contract: ``x`` is ``(..., Hk, Dh)`` — decode passes
    single tokens ``(B, 1, Hk, Dh)``, prefill whole prompts
    ``(B, S, Hk, Dh)``.  The scale is computed per leading index (one
    absmax per ``(..., Hk)`` row), so both ranks share one code path.
    Returns ``(int8 values (..., Hk, Dh), f32 scales (..., Hk))``.
    """
    assert x.ndim >= 2, f"quantize_kv wants (..., Hk, Dh), got {x.shape}"
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """Inverse of :func:`quantize_kv` (tests/oracles; decode never calls
    this — the int8 cache is consumed directly by the int8 MXU dots)."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def k_to_bitplanes(k_q: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """int8 K (B, 1, Hk, Dh) -> (planes (NBITS,B,1,Hk,Dh/8), sign (B,1,Hk,Dh/8))."""
    sign, mag = bitslice.to_sign_magnitude(k_q)
    planes = bitslice.bitplanes(mag, NBITS)
    return bitslice.pack_bits(planes, axis=-1), bitslice.pack_bits(sign, axis=-1)


def bitplanes_to_k(planes: jax.Array, sign: jax.Array) -> jax.Array:
    """Inverse (used by the exact formal-compute stage) -> int32 values."""
    mag = bitslice.from_bitplanes(bitslice.unpack_bits(planes, axis=-1))
    return bitslice.from_sign_magnitude(bitslice.unpack_bits(sign, axis=-1), mag)


# --------------------------------------------------------------------------
# slot-stack addressing — token-row bodies, heads-major scales
# --------------------------------------------------------------------------


def token_major(store: Tree, name: str) -> bool:
    """Whether leaf ``name`` of a slot stack is token-major
    ``(L, B, S, Hk*D)``: the bf16/int8 ``k``/``v`` bodies.  Their scales
    are lane-dense ``(L, B, Hk, S)`` rows, and a bgpp store (``k_planes``
    present) stays heads-major in every leaf — its plane layout is the one
    the bgpp decode reads."""
    return name in ("k", "v") and "k_planes" not in store


def _seq_index(store: Tree, name: str, idx, b, t) -> tuple:
    """Index of seq positions ``t`` of batch row(s) ``b`` in layer(s)
    ``idx`` of slot-stack leaf ``name`` (not ``k_planes``).  With ``b`` and
    ``t`` both arrays, or ``b`` a slice, both forms select the same
    ``(..., Hk*D)`` or ``(..., Hk)`` target shape (advanced dims first)."""
    if token_major(store, name):
        return (idx, b, t)
    return (idx, b, slice(None), t)


def slot_entry(store: Tree, idx, slot=None, *,
               heads: Optional[int] = None) -> Tree:
    """Layer ``idx`` of a slot stack as the heads-major entry the engine's
    jnp attends read: ``(B, Hk, S, ...)`` every leaf (``k_planes``
    ``(NBITS, B, Hk, S, D/8)``); ``slot`` (traced ok) keeps one batch row as
    a batch of one; ring ``abs_pos`` is left out.  ``heads`` is ``Hk``, the
    count the token rows of a bf16/int8 body split into (a bgpp store
    needs none).

    Token-major bodies are swapped at the read and the swap pinned as a
    materialization point, as :func:`paged_entry`'s gathered view is in the
    engine: a transpose left to fuse into the attend's dots changes their
    float reduction order on some backends, and slot and paged attends
    would no longer agree bit for bit.  The view is one layer's rows (one
    slot's in a chunk); the whole stack is never relaid."""
    out: Tree = {}
    for n, a in store.items():
        if n == "abs_pos":
            continue
        # one index op per leaf: a layer slice taken first would be copied
        # whole (every slot) before the slot is picked out of it
        if slot is None:
            a = a[idx]
        elif n == "k_planes":
            a = a[idx, :, slot][:, None]
        else:
            a = a[idx, slot][None]
        if token_major(store, n):
            a = _split_heads(a, heads)
            a = jax.lax.optimization_barrier(jnp.swapaxes(a, 1, 2))
        out[n] = a
    return out


def _split_heads(rows: jax.Array, heads: Optional[int]) -> jax.Array:
    """Token rows ``(..., Hk*D)`` -> ``(..., Hk, D)``."""
    if heads is None:
        raise ValueError("a bf16/int8 store's token rows need heads=Hk to "
                         "split into (Hk, D)")
    return rows.reshape(rows.shape[:-1] + (heads, -1))


def _kv_rows(store: Tree, k: jax.Array, v: jax.Array) -> Tree:
    """The leaves a bf16 or int8 store keeps for K/V ``(..., Hk, Dh)``:
    token rows ``(..., Hk*Dh)`` cast to the store's dtype, or int8 rows with
    f32 scales ``(..., Hk)``."""
    rows = lambda x: x.reshape(x.shape[:-2] + (-1,))
    if "k_scale" in store:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        return {"k": rows(kq), "v": rows(vq), "k_scale": ks, "v_scale": vs}
    return {"k": rows(k).astype(store["k"].dtype),
            "v": rows(v).astype(store["v"].dtype)}


# --------------------------------------------------------------------------
# paged addressing — logical position <-> physical pool row
# --------------------------------------------------------------------------
#
# The page table is ``(B, pages_per_slot)`` int32; entry ``-1`` = unmapped.
# Physical row of logical position p in slot b:
#     page_table[b, p // page_size] * page_size + p % page_size
# Write translation preserves the OOB-drop convention (OOB_INDEX lanes and
# unmapped pages scatter nowhere); read translation clamps unmapped pages to
# row 0 — every consumer masks those lanes by position anyway.


def _tok_dim(name: str) -> int:
    # pool token axis after the layer dim; the bgpp plane array interposes
    # its plane dim: (layer, plane, token, ...)
    return 2 if name == "k_planes" else 1


def phys_table(page_table: jax.Array, page_size: int, max_seq: int):
    """Gather map: ``(B, S_max)`` physical rows for every logical position
    (unmapped pages clamp to row 0 — callers mask by position)."""
    pos = jnp.arange(max_seq)
    pid = page_table[:, pos // page_size]  # (B, S)
    return jnp.where(pid >= 0, pid * page_size + (pos % page_size)[None], 0)


def _phys_write(page_table: jax.Array, tpos: jax.Array, page_size: int,
                max_seq: int, slot=None) -> jax.Array:
    """Scatter map: physical rows for logical write targets ``tpos``
    (``OOB_INDEX`` where the lane is padded / OOB / its page unmapped).

    ``slot=None``: per-slot targets — tpos ``(B,)``, one row per batch slot.
    ``slot=b`` (traced ok): tpos ``(S,)`` lanes of one slot's chunk.
    """
    n = page_table.shape[-1]
    page = jnp.clip(tpos // page_size, 0, n - 1)
    if slot is None:
        pid = page_table[jnp.arange(page_table.shape[0]), page]
    else:
        pid = jnp.take(page_table, slot, axis=0)[page]
    ok = (tpos >= 0) & (tpos < max_seq) & (pid >= 0)
    return jnp.where(ok, pid * page_size + tpos % page_size, OOB_INDEX)


def paged_entry(store: Tree, idx, phys: jax.Array, *,
                heads: Optional[int] = None) -> Tree:
    """Gather layer ``idx`` of a paged pool into the heads-major entry
    :func:`slot_entry` gives of a slot stack: phys ``(B, S)`` -> entries
    ``(B, Hk, S, ...)`` (and ``(NBITS, B, Hk, S, D/8)`` for bgpp planes);
    ``heads`` as in :func:`slot_entry`.  The gathered values are exactly
    the dense row's values, which is what keeps paged attention
    bit-identical to the slot layout."""
    out: Tree = {}
    for n, a in store.items():
        if n == "k_planes":
            g = a[idx][:, phys]  # (NBITS, B, S, Hk, D/8)
            out[n] = jnp.moveaxis(g, 3, 2)
            continue
        g = a[idx][phys]  # (B, S, Hk, ...), or (B, S, Hk*D) token rows
        if token_major(store, n):
            g = _split_heads(g, heads)
        out[n] = jnp.moveaxis(g, 2, 1)
    return out


def paged_sign(store: Tree, idx, phys: jax.Array) -> jax.Array:
    """Gather the packed sign plane of layer ``idx`` for every logical
    position: phys ``(B, S)`` -> ``(B, Hk, S, D/8)`` heads-major.

    Phase 1 of the two-phase BGPP decode: the sign plane is fetched once
    for all keys (1/8 of the int8 K bytes), before any full-precision row.
    """
    return jnp.moveaxis(store["k_sign"][idx][phys], 2, 1)


def paged_plane(store: Tree, idx, plane: int, phys: jax.Array) -> jax.Array:
    """Gather ONE packed magnitude bit-plane of layer ``idx`` for every
    logical position: phys ``(B, S)`` -> ``(B, Hk, S, D/8)`` heads-major.

    Phase 1 of the two-phase BGPP decode (round 0): only the MSB plane is
    fetched at full sequence width — 1/8 of the int8 K bytes and ~1/16 of
    a bf16 row — so the progressive predictor never touches the rest of
    the pool.
    """
    return jnp.moveaxis(store["k_planes"][idx, plane][phys], 2, 1)


def paged_rows_at(phys: jax.Array, idx: jax.Array) -> jax.Array:
    """Translate per-head logical indices through the gather map: phys
    ``(B, S)``, idx ``(B, Hk, k)`` logical positions -> ``(B, Hk, k)``
    physical pool rows (unmapped positions were already clamped to row 0
    by :func:`phys_table`; callers mask those lanes by validity)."""
    B, Hk, k = idx.shape
    return jnp.take_along_axis(phys, idx.reshape(B, Hk * k), axis=1).reshape(
        B, Hk, k
    )


def _gather_rows_per_head(al: jax.Array, rows: jax.Array, planar: bool):
    """Compacted per-(slot, head) pool gather: for each KV head ``h``,
    fetch ONLY head ``h``'s slice of pool rows ``rows[:, h]`` — the
    surviving-token fetch of BGPP phase 2, which reads ``k`` token-rows'
    worth of bytes total rather than ``k`` whole-head rows per head.

    al: ``(n_tok, Hk, ...)`` (or ``(NBITS, n_tok, Hk, ...)`` when
    ``planar``); rows: ``(B, Hk, k)`` physical rows.  Returns
    ``(B, Hk, k, ...)`` (planar: ``(NBITS, B, Hk, k, ...)``).
    """
    heads = jnp.arange(rows.shape[1])
    if planar:
        return jax.vmap(
            lambda r, h: al[:, r, h], in_axes=(1, 0), out_axes=2
        )(rows, heads)
    return jax.vmap(
        lambda r, h: al[r, h], in_axes=(1, 0), out_axes=1
    )(rows, heads)


def paged_plane_rows(store: Tree, idx, plane: int, rows: jax.Array) -> jax.Array:
    """Gather ONE packed magnitude plane at surviving physical rows only:
    rows ``(B, Hk, k)`` -> ``(B, Hk, k, D/8)``.

    Phase-1 progressive rounds r >= 1: each later round fetches the next
    plane for the shrinking candidate set (paper's early termination) —
    the plane bytes read scale with survivors, not the cache width.
    """
    return _gather_rows_per_head(store["k_planes"][idx, plane], rows, False)


def paged_topk_entry(store: Tree, idx, rows: jax.Array) -> Tree:
    """Phase-2 gather: the surviving tokens' FULL-precision bgpp rows,
    compacted.  rows ``(B, Hk, k)`` physical pool rows -> a heads-major
    entry ``{k_planes (NBITS, B, Hk, k, D/8), k_sign (B, Hk, k, D/8),
    k_scale (B, Hk, k), v (B, Hk, k, D), v_scale (B, Hk, k)}``.

    This is the only point of paged BGPP decode that touches full-precision
    K/V, and it reads exactly ``k = ceil(keep_ratio * S)`` token-rows per
    slot (each of the ``Hk`` per-head gathers fetches 1/Hk of a row).  The
    gathered values are bit-identical to slicing the same logical indices
    out of :func:`paged_entry`'s full view, which is what keeps the
    two-phase attend's logits equal to the full-gather path
    (tests/test_bgpp_gather.py).
    """
    return {
        n: _gather_rows_per_head(a[idx], rows, n == "k_planes")
        for n, a in store.items()
    }


def identity_page_table(layout: CacheLayout) -> jax.Array:
    """Slot-major mapping (slot b, page j) -> physical page b*n+j — the
    trivial table whole-batch prefill uses when no allocator is driving."""
    B, n = layout.batch, layout.pages_per_slot
    assert B * n <= layout.num_pages, "identity table exceeds the pool"
    return jnp.arange(B * n, dtype=jnp.int32).reshape(B, n)


def zero_pages(store: Tree, page_ids: jax.Array, page_size: int) -> Tree:
    """Scrub physical pages (freed by the allocator) across EVERY pool leaf
    — k/v bodies, int8 scales, bgpp bit/sign planes — in every layer.
    ``page_ids`` may be padded with ``-1`` (dropped), so one jit serves any
    eviction size."""
    tok = page_ids[:, None] * page_size + jnp.arange(page_size)[None]
    tok = jnp.where(page_ids[:, None] >= 0, tok, OOB_INDEX).reshape(-1)
    store = dict(store)
    for n, a in store.items():
        store[n] = a.at[(slice(None),) * _tok_dim(n) + (tok,)].set(0)
    return store


def zero_token_range(store: Tree, tpos: jax.Array, *, page_table=None,
                     page_size: int = 0, max_seq: int = 0) -> Tree:
    """Zero per-slot logical token positions across EVERY leaf of a global
    KV store — the speculative-decode rollback scrub.

    ``tpos`` is ``(B, N)`` int32: for each batch slot, up to ``N``
    positions whose rows held speculative writes past the accepted
    frontier; unused lanes carry :data:`OOB_INDEX` (or any out-of-range
    value) and drop, so ONE jitted scrub serves every accept pattern.

    ``page_table`` selects the paged-pool path: positions are translated
    through each slot's table row (unmapped pages — e.g. pages the
    allocator already freed wholesale — drop; :func:`zero_pages` scrubs
    those).  The slot path scatters into the per-slot ``(B, ..., S, ...)``
    stacks, covering k/v bodies, int8 scales, and bgpp sign/magnitude
    planes alike — no leaf ever keeps rolled-back contents.
    """
    safe = jnp.where((tpos >= 0) & (tpos < max_seq), tpos, OOB_INDEX)
    store = dict(store)
    if page_table is not None:
        page = jnp.clip(tpos // page_size, 0, page_table.shape[-1] - 1)
        pid = jnp.take_along_axis(page_table, page, axis=1)  # (B, N)
        ok = (tpos >= 0) & (tpos < max_seq) & (pid >= 0)
        phys = jnp.where(
            ok, pid * page_size + tpos % page_size, OOB_INDEX
        ).reshape(-1)
        for n, a in store.items():
            store[n] = a.at[(slice(None),) * _tok_dim(n) + (phys,)].set(0)
        return store
    bidx = jnp.arange(tpos.shape[0])[:, None]  # (B, 1) against (B, N) lanes
    for n, a in store.items():
        if n == "k_planes":  # (L, NBITS, B, Hk, S, D/8)
            store[n] = a.at[:, :, bidx, :, safe].set(0)
        else:
            store[n] = a.at[_seq_index(store, n, slice(None), bidx, safe)].set(0)
    return store


def page_bytes(store: Tree, page_size: int) -> int:
    """Bytes one physical page occupies across every leaf of a pool (host
    arithmetic from shapes — the allocator's resident-KV accounting)."""
    total = 0
    for n, a in store.items():
        n_tok = a.shape[_tok_dim(n)]
        total += a.size * a.dtype.itemsize * page_size // n_tok
    return total


# --------------------------------------------------------------------------
# KV-read accounting — bytes the jitted steps gather from the KV stores
# --------------------------------------------------------------------------
#
# Host-side mirrors of the device gathers, computed from the SAME static
# shapes the jitted steps address (B rows × layer stacks × the per-format
# row bytes; for bgpp, the two-phase plan: sign + MSB plane at full width,
# one shrinking survivor plane per progressive round, then ceil(keep·S)
# full-precision rows).  The scheduler accumulates these per executed step
# into ``Scheduler.stats()["kv_read"]`` — the counter the serving
# benchmarks and launchers report, and the one the acceptance assert
# (paged bgpp reads bit-planes + at most k_max full rows) checks against.


def _cache_dtype_bytes(cfg) -> int:
    return 2 if cfg.dtype == "bfloat16" else 4


def _token_row_bytes(cfg, fmt: str) -> float:
    """Bytes one token's KV row (all ``Hk`` heads, K and V sides plus any
    scales) occupies in a stack of format ``fmt``."""
    Hk, Dh = cfg.num_kv_heads, cfg.head_dim
    if fmt == "bf16":
        return Hk * Dh * _cache_dtype_bytes(cfg) * 2.0
    if fmt == "int8":
        return Hk * (2.0 * Dh + 8.0)  # int8 K+V, two f32 scales
    if fmt == "bgpp":
        # packed magnitude planes + sign plane + f32 k_scale + int8 V + f32
        # v_scale — the FULL row phase 2 fetches per surviving token
        return Hk * (NBITS * Dh / 8.0 + Dh / 8.0 + 4.0 + Dh + 4.0)
    raise ValueError(fmt)


def mesh_shard_factors(layout: CacheLayout, cfg, mesh_shape) -> Tuple[int, int]:
    """``(d_eff, m_eff)``: how many ways a ``(data, model)`` mesh actually
    splits the serve_step KV reads.  Mirrors :meth:`ShardingRules
    .spec_for_shape` divisibility fallback — a ``"model"`` axis that does
    not divide BOTH head counts replicates (q must shard alongside k/v for
    the attend to stay device-local), and a ``"data"`` axis that does not
    divide the batch replicates."""
    d, m = int(mesh_shape[0]), int(mesh_shape[1])
    Hq, Hk = cfg.num_heads, cfg.num_kv_heads
    m_eff = m if m >= 1 and Hk and Hq and Hk % m == 0 and Hq % m == 0 else 1
    d_eff = d if d >= 1 and layout.batch % d == 0 else 1
    return d_eff, m_eff


# dtype of the tensor-parallel all-reduces (engine._row_parallel): on a
# "model" axis above 1 each device's partial sum of a row-parallel
# projection (wo, the MLP's down) is kept in float32 and rounded to the
# activation dtype once, after the sum, as one device's dot rounds its
# float32 accumulator once.  Decode activations are float32 already; a
# prefill chunk's bf16 (C, d_model) partials double in bytes.
TP_SUM_DTYPE = jnp.float32


def _tp_allreduce(rows: float, cfg, shards: Dict[str, Tuple[int, int]],
                  groups: int) -> Dict[str, float]:
    """Bytes the tensor-parallel all-reduces of one step move, over the
    whole mesh: one after each row-parallel projection of every layer,
    ``wo`` (``attn_out_allreduce``) and a dense MLP's ``down``
    (``mlp_out_allreduce``), each of ``rows`` activation rows of d_model
    :data:`TP_SUM_DTYPE` partial sums.  ``shards`` gives each projection's
    (input-row, output-column) shard counts as the placement made them
    (``repro.serving.sharded.projection_shards``).  A ring all-reduce over
    ``r`` row shards sends ``2 * (r - 1) / r`` of the array from each of
    them, so a group moves ``2 * (r - 1) * rows * d_model * itemsize``
    bytes; ``groups`` groups (one per data replica that computes those
    rows) each move as much.  A projection kept whole (``r == 1``: no
    mesh, or the placement's divisibility fallback) sums nothing."""
    per_row = cfg.d_model * jnp.dtype(TP_SUM_DTYPE).itemsize * groups
    out = {}
    for proj, key in (("wo", "attn_out_allreduce"),
                      ("down", "mlp_out_allreduce")):
        r = shards.get(proj, (1, 1))[0]
        out[key] = 2.0 * (r - 1) * rows * per_row * cfg.num_layers
    return out


def _interconnect_decode(layout: CacheLayout, cfg, mesh_shape, d_eff: int,
                         shards) -> Dict[str, float]:
    """Collective bytes ONE batched serve_step moves between devices.

    * ``attn_out_allreduce`` / ``mlp_out_allreduce`` — the two
      tensor-parallel all-reduces of each layer (:func:`_tp_allreduce`)
      over the step's ``B`` rows; a batch the ``"data"`` axis cannot split
      is reduced by every data replica.
    * ``paged_write_bcast`` — paged pools have no batch axis, so they are
      replicated across ``"data"``; the B decode-token KV rows (computed
      batch-sharded) must reach every data replica of the pool.
    """
    B = layout.batch
    out = _tp_allreduce(B, cfg, shards, int(mesh_shape[0]) // d_eff)
    ng = len(layout.global_layers)
    out["paged_write_bcast"] = 0.0
    if layout.layout == "paged" and ng:
        out["paged_write_bcast"] = ((d_eff - 1) * B * ng
                                    * _token_row_bytes(cfg, layout.kv_format))
    out["total"] = sum(out.values())
    return out


def bgpp_decode_plan(S: int, cfg) -> Tuple[int, int, Tuple[int, ...]]:
    """Static shapes of one two-phase BGPP decode attend over ``S`` cache
    lanes, per (row, layer): ``(rounds, k_max, survivors)`` with
    ``survivors[r]`` the candidate-set width whose plane round ``r``
    fetches (``S`` at round 0, then ``max(k_max, S >> r)``).

    This is THE definition of the plan: ``engine._bgpp_topk_indices``
    takes its round/top-k widths from here, and :func:`decode_read_bytes`
    prices the same tuple — so the reported counter can never drift from
    the shapes the engine actually gathers."""
    mo = cfg.mcbp
    if S < 1:
        raise ValueError(
            f"bgpp_decode_plan: cache width S={S} must be >= 1 — was the "
            f"layout built with max_seq=0?"
        )
    if mo.bgpp_rounds < 1:
        raise ValueError(
            f"bgpp_decode_plan: bgpp_rounds={mo.bgpp_rounds} must be >= 1 "
            f"(round 0 always scans the MSB plane)"
        )
    if not (0.0 < mo.bgpp_keep_ratio <= 1.0):
        raise ValueError(
            f"bgpp_decode_plan: bgpp_keep_ratio={mo.bgpp_keep_ratio} must "
            f"be in (0, 1] — it sizes the surviving candidate set"
        )
    rounds = max(1, min(mo.bgpp_rounds, NBITS))
    k_max = max(1, min(S, int(math.ceil(mo.bgpp_keep_ratio * S))))
    survivors = (S,) + tuple(max(k_max, S >> r) for r in range(1, rounds))
    return rounds, k_max, survivors


def decode_read_bytes(layout: CacheLayout, cfg,
                      mesh_shape: Tuple[int, int] = (1, 1),
                      shards: Optional[Dict[str, Tuple[int, int]]] = None,
                      ) -> Dict[str, Any]:
    """KV bytes ONE batched ``serve_step`` gathers, at its static shapes.

    All ``layout.batch`` rows and every cached layer are counted (the
    jitted step gathers them regardless of slot liveness — static shapes).
    Global bf16/int8 stacks read the full ``(S_max,)`` row; local rings
    read their ``window``; bgpp global stacks follow the two-phase plan:
    sign + MSB plane everywhere, shrinking survivor planes, then exactly
    ``k_max = ceil(bgpp_keep_ratio * S_max)`` full-precision token rows
    per (slot, layer) — reported under ``"bgpp"`` so callers can assert
    the full-row fetch never exceeds the keep ratio.  ``"bf16_equiv"`` is
    what a bf16 cache of the same geometry would read — the reduction
    denominator the benchmarks report.

    With a ``(data, model)`` ``mesh_shape``, two extra sections appear:
    ``"per_device"`` (the same counters divided by the effective shard
    count — reads are batch-sharded over ``"data"`` and head-sharded over
    ``"model"``, so each device gathers ``total / (d_eff * m_eff)`` bytes)
    and ``"interconnect"`` (see :func:`_interconnect_decode`; ``shards``
    are the projections' shard counts, :func:`_tp_allreduce`).  At 1×1
    per-device equals total and interconnect is zero.
    """
    B, S, W = layout.batch, layout.max_seq, layout.local_window
    ng, nl = len(layout.global_layers), len(layout.local_layers)
    out: Dict[str, Any] = {"global": 0.0, "local": 0.0}
    if ng:
        if layout.kv_format == "bgpp":
            rounds, k_max, survivors = bgpp_decode_plan(S, cfg)
            plane_row = cfg.num_kv_heads * cfg.head_dim / 8.0
            sign = S * plane_row
            planes = float(sum(survivors)) * plane_row
            topk_full = k_max * _token_row_bytes(cfg, "bgpp")
            out["bgpp"] = {
                "rounds": rounds,
                "full_rows_per_slot": k_max,
                "sign_bytes": B * ng * sign,
                "plane_bytes": B * ng * planes,
                "topk_full_bytes": B * ng * topk_full,
            }
            out["global"] = B * ng * (sign + planes + topk_full)
        else:
            out["global"] = B * ng * S * _token_row_bytes(cfg, layout.kv_format)
    if nl:
        fmt_l = "int8" if layout.kv_format == "bgpp" else layout.kv_format
        out["local"] = B * nl * W * _token_row_bytes(cfg, fmt_l)
    out["total"] = out["global"] + out["local"]
    out["bf16_equiv"] = (B * ng * S + B * nl * W) * _token_row_bytes(cfg, "bf16")
    d_eff, m_eff = mesh_shard_factors(layout, cfg, mesh_shape)
    kv_shards = d_eff * m_eff
    out["per_device"] = {
        "global": out["global"] / kv_shards,
        "local": out["local"] / kv_shards,
        "total": out["total"] / kv_shards,
        "shards": kv_shards,
    }
    out["interconnect"] = _interconnect_decode(layout, cfg, mesh_shape, d_eff,
                                               shards or {})
    return out


def chunk_read_bytes(layout: CacheLayout, cfg,
                     mesh_shape: Tuple[int, int] = (1, 1),
                     chunk_width: int = 1,
                     shards: Optional[Dict[str, Tuple[int, int]]] = None,
                     ) -> Dict[str, Any]:
    """KV bytes ONE chunked-prefill step reads from the live cache (one
    slot): global layers attend the full ``(S_max,)`` row at full precision
    — BGPP's progressive prediction is a decode-time saving; prefill
    reconstructs exact int8 K from every plane — and local ring layers
    gather their ``window``.  Eager admission reads nothing (the B=1
    forward self-attends without touching the cache).  ``mesh_shape`` and
    ``shards`` price the mesh columns as in :func:`decode_read_bytes`."""
    S, W = layout.max_seq, layout.local_window
    ng, nl = len(layout.global_layers), len(layout.local_layers)
    fmt_l = "int8" if layout.kv_format == "bgpp" else layout.kv_format
    g = ng * S * _token_row_bytes(cfg, layout.kv_format)
    loc = nl * W * _token_row_bytes(cfg, fmt_l)
    out: Dict[str, Any] = {"global": g, "local": loc, "total": g + loc}
    # chunks run at B=1, so only the "model" head shard splits the reads
    _, m_eff = mesh_shard_factors(layout, cfg, mesh_shape)
    out["per_device"] = {"total": out["total"] / m_eff, "shards": m_eff}
    # a B=1 chunk is replicated across "data": every data replica computes
    # it, reduces its own partial sums and writes its own pool copy (no
    # paged write broadcast)
    ic = _tp_allreduce(chunk_width, cfg, shards or {}, int(mesh_shape[0]))
    ic["paged_write_bcast"] = 0.0
    ic["total"] = sum(ic.values())
    out["interconnect"] = ic
    return out


# --------------------------------------------------------------------------
# stack writes — the ONE code path for bf16 / int8 / bgpp stores
# --------------------------------------------------------------------------
#
# The storage format is inferred from the store's keys (``k_planes`` => bgpp,
# ``k_scale`` => int8, else bf16), so decode layers and both prefill paths
# (whole-batch and single-slot admission) never branch on format themselves.


def write_token(store: Tree, idx: int, k: jax.Array, v: jax.Array,
                tpos: jax.Array, *, page_table=None, page_size: int = 0,
                max_seq: int = 0) -> Tree:
    """Write one decode token into layer ``idx`` of a KV stack, per slot.

    k/v: fresh projections ``(B, 1, Hk, Dh)`` (seq-major).
    tpos: ``(B,)`` int32 per-slot target index along the stack's seq axis —
    the absolute position for global stacks, ``pos % window`` for local
    ring buffers.  Every batch row scatters to its own index, which is what
    lets staggered requests share one cache.

    ``page_table`` selects the paged-pool path: tpos is translated through
    the slot's table row (unmapped page => dropped write) and the scatter
    targets the token-major pool.
    """
    B = k.shape[0]
    if page_table is not None:
        # (B, 1) targets broadcast against the (B, 1, Hk, ...) projections,
        # so the shared paged scatter tail serves decode writes too
        phys = _phys_write(page_table, tpos, page_size, max_seq)
        return _scatter_paged_kv(store, idx, phys[:, None], k, v)
    bidx = jnp.arange(B)
    if "k_planes" in store:  # bgpp: bit-planed K magnitudes + int8 V
        kq, ks = quantize_kv(k)
        planes, sign = k_to_bitplanes(kq)  # (NBITS,B,1,Hk,D/8), (B,1,Hk,D/8)
        store["k_planes"] = store["k_planes"].at[idx, :, bidx, :, tpos].set(
            jnp.moveaxis(planes[:, :, 0], 0, 1))  # (B,NBITS,Hk,D/8)
        store["k_sign"] = store["k_sign"].at[idx, bidx, :, tpos].set(sign[:, 0])
        store["k_scale"] = store["k_scale"].at[idx, bidx, :, tpos].set(ks[:, 0])
        vq, vs = quantize_kv(v)
        store["v"] = store["v"].at[idx, bidx, :, tpos].set(vq[:, 0])
        store["v_scale"] = store["v_scale"].at[idx, bidx, :, tpos].set(vs[:, 0])
    else:  # int8 / bf16: one (Hk, D) row per slot, (Hk,) scales
        for n, x in _kv_rows(store, k, v).items():
            if token_major(store, n):
                store[n] = store[n].at[idx, bidx, tpos].set(x[:, 0])
                continue
            # a (B, Hk, S) scale row takes its lane by a select over the
            # layer: XLA lays a scatter into it out token-major, and the
            # decode kernel reads the stack as stored, so a scatter would
            # relay the whole stack around every layer's attend
            lane = jnp.arange(store[n].shape[-1]) == tpos[:, None, None]
            store[n] = store[n].at[idx].set(
                jnp.where(lane, x[:, 0, :, None], store[n][idx]))
    return store


def _scatter_chunk_kv(store: Tree, idx: int, slot, tpos, k, v) -> Tree:
    """Quantize-and-scatter one chunk's K/V rows (int8 or bf16 stores) into
    seq indices ``tpos`` of batch row ``slot`` — the shared tail of both
    chunked write paths (each leaf's index selects ``(S, Hk, ...)``; OOB
    lanes drop)."""
    for n, x in _kv_rows(store, k, v).items():
        store[n] = store[n].at[_seq_index(store, n, idx, slot, tpos)].set(x[0])
    return store


def _scatter_paged_kv(store: Tree, idx, phys, k, v) -> Tree:
    """Quantize-and-scatter K/V token rows into pool rows ``phys`` (any
    shape matching k/v's leading batch/seq dims; OOB rows drop).  Values
    stay token-major — the pool's native order, so no transposes."""
    if "k_planes" in store:
        kq, ks = quantize_kv(k)
        planes, sign = k_to_bitplanes(kq)  # (NBITS, *phys.shape, Hk, D/8)
        store["k_planes"] = store["k_planes"].at[idx, :, phys].set(
            jnp.moveaxis(planes, 0, phys.ndim))
        store["k_sign"] = store["k_sign"].at[idx, phys].set(sign)
        store["k_scale"] = store["k_scale"].at[idx, phys].set(ks)
        vq, vs = quantize_kv(v)
        store["v"] = store["v"].at[idx, phys].set(vq)
        store["v_scale"] = store["v_scale"].at[idx, phys].set(vs)
    else:
        for n, x in _kv_rows(store, k, v).items():
            store[n] = store[n].at[idx, phys].set(x)
    return store


def write_prefill(store: Tree, idx: int, k: jax.Array, v: jax.Array,
                  *, slot: Optional[int] = None, offset=None,
                  length=None, page_table=None, page_size: int = 0,
                  max_seq: int = 0) -> Tree:
    """Write a whole prompt's K/V into positions ``[0, S)`` of a global stack.

    k/v: ``(B, S, Hk, Dh)``.  ``slot=None`` writes every batch row (fresh
    whole-batch prefill); ``slot=b`` writes row ``b`` only — admission of one
    prompt (``B == 1``) into a single slot of a *live* cache.

    ``offset``/``length`` select the chunked-admission path: the ``S`` lanes
    are a fixed-shape prefill chunk whose first ``length`` lanes are valid
    prompt tokens landing at positions ``[offset, offset+length)``; padded
    lanes scatter to :data:`OOB_INDEX` and are dropped.  ``slot``/``offset``/
    ``length`` may all be traced scalars, so one jitted chunk step serves
    every slot and token offset (compiled once per chunk width ``S``).

    ``page_table`` selects the paged-pool path: every logical target is
    translated through the table (same OOB-drop convention; writes to
    unmapped pages vanish) and scattered token-major into the pool.
    """
    S = k.shape[1]
    if page_table is not None:
        if offset is not None:
            assert slot is not None and k.shape[0] == 1, \
                "chunked writes admit one prompt into one slot"
            length = S if length is None else length
            lane = jnp.arange(S)
            tpos = jnp.where(lane < length, offset + lane, OOB_INDEX)
            phys = _phys_write(page_table, tpos, page_size, max_seq, slot=slot)
            return _scatter_paged_kv(store, idx, phys, k[0], v[0])
        lanes = jnp.arange(S)
        if slot is None:
            pid = page_table[:, lanes // page_size]  # (B, S)
            phys = jnp.where(pid >= 0,
                             pid * page_size + (lanes % page_size)[None],
                             OOB_INDEX)
            return _scatter_paged_kv(store, idx, phys, k, v)
        assert k.shape[0] == 1, "slot admission writes one prompt at a time"
        phys = _phys_write(page_table, lanes, page_size, max_seq, slot=slot)
        return _scatter_paged_kv(store, idx, phys, k[0], v[0])
    if offset is not None:
        assert slot is not None and k.shape[0] == 1, \
            "chunked writes admit one prompt into one slot"
        length = S if length is None else length
        lane = jnp.arange(S)
        tpos = jnp.where(lane < length, offset + lane, OOB_INDEX)
        if "k_planes" in store:
            kq, ks = quantize_kv(k)
            planes, sign = k_to_bitplanes(kq)  # (NBITS,1,S,Hk,D/8)
            # .at[idx, :, slot, :, tpos] selects (S, NBITS, Hk, D/8)
            store["k_planes"] = store["k_planes"].at[idx, :, slot, :, tpos].set(
                jnp.moveaxis(planes[:, 0], 0, 1))
            store["k_sign"] = store["k_sign"].at[idx, slot, :, tpos].set(sign[0])
            store["k_scale"] = store["k_scale"].at[idx, slot, :, tpos].set(ks[0])
            vq, vs = quantize_kv(v)
            store["v"] = store["v"].at[idx, slot, :, tpos].set(vq[0])
            store["v_scale"] = store["v_scale"].at[idx, slot, :, tpos].set(vs[0])
            return store
        return _scatter_chunk_kv(store, idx, slot, tpos, k, v)
    if slot is None:
        bsel: Any = slice(None)
        lead = lambda a: a
    else:
        assert k.shape[0] == 1, "slot admission writes one prompt at a time"
        bsel = slot
        lead = lambda a: a[0]
    tr = lambda a: lead(jnp.swapaxes(a, 1, 2))  # (B,S,Hk,...) -> (B,Hk,S,...)
    if "k_planes" in store:
        kq, ks = quantize_kv(k)
        planes, sign = k_to_bitplanes(kq)  # (NBITS,B,S,Hk,D/8)
        ptr = (lambda a: jnp.swapaxes(a, 2, 3)) if slot is None else (
            lambda a: jnp.swapaxes(a, 2, 3)[:, 0])
        store["k_planes"] = store["k_planes"].at[idx, :, bsel, :, :S].set(ptr(planes))
        store["k_sign"] = store["k_sign"].at[idx, bsel, :, :S].set(tr(sign))
        store["k_scale"] = store["k_scale"].at[idx, bsel, :, :S].set(tr(ks))
        vq, vs = quantize_kv(v)
        store["v"] = store["v"].at[idx, bsel, :, :S].set(tr(vq))
        store["v_scale"] = store["v_scale"].at[idx, bsel, :, :S].set(tr(vs))
        return store
    for n, x in _kv_rows(store, k, v).items():
        if token_major(store, n):
            store[n] = store[n].at[idx, bsel, :S].set(lead(x))
        else:
            store[n] = store[n].at[idx, bsel, :, :S].set(tr(x))
    return store


def write_prefill_local(store: Tree, idx: int, k: jax.Array, v: jax.Array,
                        window: int, *, slot: Optional[int] = None,
                        offset=None, length=None) -> Tree:
    """Ring-write the last ``min(window, S)`` prompt positions of a local
    stack (slot ``pos % window``), recording absolute positions for
    RoPE-correct reuse.  ``slot`` selects one batch row as in
    :func:`write_prefill`.

    ``offset``/``length`` (traced ok) select the chunked-admission path:
    lanes are chunk tokens at positions ``[offset, offset+length)``.  Only
    the last ``min(length, window)`` valid lanes are written — the earlier
    ones would be ring-evicted by them anyway, and masking them keeps the
    kept lanes' ring slots unique so the scatter has no write races.
    """
    B, S = k.shape[:2]
    if offset is not None:
        assert slot is not None and B == 1, \
            "chunked writes admit one prompt into one slot"
        length = S if length is None else length
        lane = jnp.arange(S)
        keep = (lane < length) & (lane >= length - window)
        tpos = jnp.where(keep, jnp.mod(offset + lane, window), OOB_INDEX)
        store = _scatter_chunk_kv(store, idx, slot, tpos, k, v)
        store["abs_pos"] = store["abs_pos"].at[idx, slot, tpos].set(offset + lane)
        return store
    take = min(window, S)
    pos_abs = jnp.arange(S - take, S)
    slots = jnp.mod(pos_abs, window)
    k, v = k[:, -take:], v[:, -take:]
    if slot is None:
        bsel: Any = slice(None)
        # the ring index targets (take, B, Hk, ...) — advanced dims first
        tr = lambda a: jnp.swapaxes(a, 0, 1)
    else:
        assert B == 1
        bsel = slot
        # .. and (take, Hk, ...) for one slot
        tr = lambda a: a[0]
    for n, x in _kv_rows(store, k, v).items():
        store[n] = store[n].at[_seq_index(store, n, idx, bsel, slots)].set(tr(x))
    if slot is None:
        store["abs_pos"] = store["abs_pos"].at[idx, :, slots].set(
            jnp.broadcast_to(pos_abs, (B, take)).T)
    else:
        store["abs_pos"] = store["abs_pos"].at[idx, slot, slots].set(pos_abs)
    return store


# --------------------------------------------------------------------------
# slot lifecycle
# --------------------------------------------------------------------------


def _batch_dim(stack: str, name: str) -> int:
    # all stacks put batch at dim 1 except the bgpp plane array, whose
    # leading dims are (layer, plane, batch, ...)
    return 2 if name == "k_planes" else 1


def reset_slot(cache: Tree, layout: CacheLayout, slot: int) -> Tree:
    """Clear one batch row across every stack without touching live
    neighbors: KV rows to zero, ring ``abs_pos`` to -1 (nothing valid),
    mamba state to zero, ``pos[slot]`` to 0.  This is eviction; admission is
    ``engine.prefill_into_slot`` (which calls this first, so stale ring
    positions from the previous occupant can never alias into the new
    request's valid window).

    Paged layouts: the global pool has no batch rows — page lifecycle
    (decref, free, zero) belongs to :class:`repro.serving.paging
    .PageAllocator`, and the device page table is synced from its host
    copy, so this clears only the slot-major state (local rings, mamba,
    cross, pos).
    """

    def _clear(a, bdim, fill=0):
        return a.at[(slice(None),) * bdim + (slot,)].set(fill)

    cache = dict(cache)
    stacks = ("local",) if layout.layout == "paged" else ("global", "local")
    for stack in stacks:
        if stack not in cache:
            continue
        st = dict(cache[stack])
        for n, a in st.items():
            st[n] = _clear(a, _batch_dim(stack, n),
                           fill=-1 if n == "abs_pos" else 0)
        cache[stack] = st
    if "mamba" in cache:
        cache["mamba"] = {
            n: _clear(a, 1) for n, a in cache["mamba"].items()
        }
    for n in ("cross_k", "cross_v"):
        if n in cache:
            cache[n] = _clear(cache[n], 1)
    cache["pos"] = cache["pos"].at[slot].set(0)
    return cache
