"""Serve-time weight formats: the ``weight_format`` knob made real.

``MCBPOptions.weight_format`` selects the numerics of the decode-time
projections (``wq``/``wk``/``wv``/``wo``, the dense MLP, ``lm_head``):

* ``"bf16"`` — the default: raw parameter leaves, every op bit-for-bit
  identical to the pre-knob engine (nothing here ever touches them);
* ``"int8"`` — per-output-channel symmetric int8 quantization.  Each
  projection leaf is replaced by a ``{"q": int8, "scale": f32}`` record;
  ``repro.models.layers.wdot`` dequantizes it at trace time, so the serve
  logits are pinned to the dense-reconstruction oracle (running the bf16
  path on the dequantized weights is bit-identical);
* ``"bstc"`` — the paper's BS-sparsity two-state coding.  The SAME int8
  records serve the values (BSTC is lossless over the int8 weight —
  ``reconstruct_dense_weight`` is a property-test law), while the
  :class:`WeightPlan` prices HBM traffic from the actual coded layout
  measured by ``repro.core.bstc.encode_weight`` / the
  ``repro.kernels.bstc_matmul`` operand prep.  ``prepare_serve_params``
  round-trips one matrix through the kernel family's compressed operands
  and asserts the reconstruction matches, so serve values genuinely pass
  through the BSTC code path rather than trusting the law blindly.

Resolution happens ONCE at ``make_serve_step`` build time
(:func:`resolve`), exactly like the ``decode_kernel`` knob: the config
value, overridden by the ``REPRO_WEIGHT_FORMAT`` env var for CI matrices.
An unknown value raises with the same actionable message style.

Accounting mirrors ``kv_cache.decode_read_bytes``: the scheduler holds a
:class:`WeightPlan` and accumulates its static per-step byte totals per
executed decode step into ``Scheduler.stats()["weight_read"]`` — totals,
a bf16-equivalent denominator, a per-projection breakdown, closed-form
modeled bytes (``repro.analysis.roofline.bstc_weight_traffic``, gated
against the measured coded bytes at 1.0 ± 10%), and mesh columns priced
from the shard counts the serving placement gives each projection
(``repro.serving.sharded.projection_shards``).
"""

from __future__ import annotations

import dataclasses
import math
import os
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import roofline
from repro.configs.base import WEIGHT_FORMATS
from repro.core import bstc

Tree = Dict[str, Any]

ENV_VAR = "REPRO_WEIGHT_FORMAT"
FORMATS = WEIGHT_FORMATS

# projection leaves the serve path converts (explicit names: biases and
# norms stay raw, MoE expert banks stay bf16 — a documented limitation)
_ATTN_WEIGHTS = ("wq", "wk", "wv", "wo")
_MLP_WEIGHTS = ("gate", "up", "down")


def resolve(cfg) -> str:
    """Resolve the ``weight_format`` knob to one of :data:`FORMATS`.

    ``REPRO_WEIGHT_FORMAT`` overrides the config so CI matrices can flip
    the weight path without touching configs — same contract as
    ``kernel_decode.resolve``.  The config value itself was validated at
    construction (``MCBPOptions.__post_init__``), so only env values can
    reach the error here.
    """
    knob = os.environ.get(ENV_VAR, "").strip() or getattr(
        cfg.mcbp, "weight_format", "bf16"
    )
    if knob not in FORMATS:
        raise ValueError(
            f"weight_format={knob!r} is not one of {FORMATS} (config "
            f"mcbp.weight_format or ${ENV_VAR})"
        )
    return knob


def validate(cfg) -> None:
    """Raise an actionable config-level error for unservable combinations.

    Called once at ``make_serve_step`` build time when the resolved format
    is not ``bf16`` — the converted-record path covers the transformer
    families the scheduler serves.
    """
    if cfg.family not in ("dense", "moe", "vlm"):
        raise ValueError(
            f"weight_format={resolve(cfg)!r} covers the transformer serve "
            f"families (dense/moe/vlm); family={cfg.family!r} decodes with "
            f"raw bf16 weights — set weight_format='bf16'"
        )


def is_record(w) -> bool:
    """True for a ``{"q", "scale"}`` quantized-weight record leaf."""
    return isinstance(w, dict) and "q" in w and "scale" in w


def quantize(w) -> Tree:
    """Per-output-channel symmetric int8 record for a ``(..., in, out)``
    weight (leading axes = stacked layer copies).

    ``scale = max|w| / 127`` over the input (contraction) axis — exact
    elementwise math and a max, so a column- or row-sharded input yields
    an identically valued (and identically sharded) record; the scales
    stay one per output column.
    """
    wf = jnp.asarray(w).astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(wf), axis=-2), 1e-12) / 127.0
    q = jnp.clip(jnp.round(wf / scale[..., None, :]), -127, 127)
    return {"q": q.astype(jnp.int8), "scale": scale}


def dequantize(record: Tree, dtype=jnp.float32) -> jax.Array:
    """The dense reconstruction ``layers.wdot`` contracts against — THE
    oracle the int8/bstc serve parity tests pin to."""
    return (
        record["q"].astype(jnp.float32)
        * record["scale"][..., None, :].astype(jnp.float32)
    ).astype(dtype)


def check_serve_params(params: Tree, cfg, fmt: str) -> None:
    """Trace-time structural check inside ``serve_step``: a non-bf16 build
    must receive converted records, never raw leaves (the pre-fix bug was
    exactly this silent pass-through)."""
    lay = params.get("layers", {})
    probe = lay.get("attn", {}).get("wq") if isinstance(lay, dict) else None
    if probe is not None and not is_record(probe):
        raise ValueError(
            f"serve_step was built with weight_format={fmt!r} but received "
            f"raw weight leaves — convert them first with "
            f"repro.serving.weights.prepare_serve_params(params, cfg, "
            f"layout) (the Scheduler does this automatically)"
        )


# --------------------------------------------------------------------------
# the weight-read plan — host-side byte accounting, kv_read's mirror
# --------------------------------------------------------------------------


@dataclasses.dataclass
class WeightEntry:
    """One converted projection (all its stacked layer copies).

    Byte columns cover ALL ``copies``.
    """

    path: str
    proj: str
    copies: int
    in_dim: int
    out_dim: int
    coded_bytes: float
    int8_bytes: float
    bf16_bytes: float
    modeled_bytes: float
    bstc_fallback: bool = False  # dims indivisible: priced as plain int8


@dataclasses.dataclass
class WeightPlan:
    """Static per-step weight traffic of one built serve path.

    The jitted ``serve_step`` contracts every converted projection exactly
    once per batched decode step (weights are step-invariant — this is the
    memory-bound half of decode), so per-step pricing is the plan's
    per-matrix coded bytes summed; the scheduler multiplies by executed
    steps, exactly like ``kv_read``.
    """

    fmt: str
    entries: List[WeightEntry]

    def _sum(self, col: str) -> float:
        return float(sum(getattr(e, col) for e in self.entries))

    @property
    def total_bytes(self) -> float:
        """Coded bytes one decode step reads across every converted matrix."""
        return self._sum("coded_bytes")

    @property
    def bf16_bytes(self) -> float:
        """What raw-dtype leaves of the same geometry would read."""
        return self._sum("bf16_bytes")

    def decode_read_bytes(
            self, shards: Optional[Dict[str, Tuple[int, int]]] = None,
    ) -> Dict[str, Any]:
        """Per-step decode weight traffic, as the scheduler reports it.

        Mirrors :func:`repro.serving.kv_cache.decode_read_bytes`: totals,
        the bf16-equivalent denominator, per-projection breakdown, the
        closed-form modeled bytes, and mesh columns.  ``shards`` maps a
        projection to its (input-row, output-column) shard counts on the
        mesh (:func:`repro.serving.sharded.projection_shards`; absent: one
        device holds it whole), so a device reads ``1 / (rows * columns)``
        of each entry.  The norms the plan leaves out are replicated.
        """
        shards = shards or {}

        def ways(e: WeightEntry) -> int:
            rows, cols = shards.get(e.proj, (1, 1))
            return rows * cols

        per_proj: Dict[str, float] = {}
        per_dev_proj: Dict[str, float] = {}
        shards_proj: Dict[str, int] = {}
        for e in self.entries:
            per_proj[e.proj] = per_proj.get(e.proj, 0.0) + e.coded_bytes
            per_dev_proj[e.proj] = (per_dev_proj.get(e.proj, 0.0)
                                    + e.coded_bytes / ways(e))
            shards_proj[e.proj] = ways(e)
        sharded = sum(e.coded_bytes / ways(e) for e in self.entries
                      if ways(e) > 1)
        replicated = sum(e.coded_bytes for e in self.entries
                         if ways(e) == 1)
        return {
            "format": self.fmt,
            "total": self.total_bytes,
            "bf16_equiv": self.bf16_bytes,
            "int8_equiv": self._sum("int8_bytes"),
            "modeled": self._sum("modeled_bytes"),
            "per_projection": per_proj,
            "per_device": {
                "sharded": sharded,
                "replicated": replicated,
                "total": sharded + replicated,
                "shards": max((ways(e) for e in self.entries), default=1),
            },
            # the accounting-law surface: summing
            # per_device_by_projection[p] * shards_by_projection[p] over
            # projections recovers the total on every mesh
            "per_device_by_projection": per_dev_proj,
            "shards_by_projection": shards_proj,
        }


# --------------------------------------------------------------------------
# serve-params preparation
# --------------------------------------------------------------------------


def projection_paths(params: Tree) -> Iterator[Tuple[Tuple[str, ...], str]]:
    """Yield ``(path, proj_name)`` for every convertible leaf present in
    the tree (explicit names only — biases/norms/MoE stay raw)."""
    lay = params.get("layers")
    if isinstance(lay, dict):
        attn = lay.get("attn")
        if isinstance(attn, dict):
            for n in _ATTN_WEIGHTS:
                if n in attn:
                    yield ("layers", "attn", n), n
        mlp = lay.get("mlp")
        if isinstance(mlp, dict):
            for n in _MLP_WEIGHTS:
                if n in mlp:
                    yield ("layers", "mlp", n), n
    if "lm_head" in params:
        yield ("lm_head",), "lm_head"


def get_path(tree: Tree, path: Tuple[str, ...]):
    """The leaf at ``path`` (a :func:`projection_paths` path) of ``tree``."""
    for k in path:
        tree = tree[k]
    return tree


def _set(tree: Tree, path: Tuple[str, ...], value) -> Tree:
    """Copy-on-write set: shallow-copies only the dicts along ``path`` so
    the caller's raw params tree is never mutated."""
    out = dict(tree)
    node = out
    for k in path[:-1]:
        node[k] = dict(node[k])
        node = node[k]
    node[path[-1]] = value
    return out


def _dtype_bytes(cfg) -> int:
    return 2 if cfg.dtype == "bfloat16" else 4


def _bstc_matrix_bytes(q_np: np.ndarray, scale_np: np.ndarray,
                       cfg) -> Optional[Tuple[float, float]]:
    """Measured + modeled coded bytes of ONE ``(in, out)`` int8 matrix.

    Encodes the transposed ``(out, in)`` weight channel-major (scale is
    per output channel) with the paper's group size ``m``.  Returns
    ``None`` when the dims don't divide the coding grid (``out % m`` or
    ``in % 8``) — the caller prices that matrix as plain int8 instead of
    asserting, so odd smoke geometries still serve.
    """
    in_dim, out_dim = q_np.shape
    m = int(cfg.mcbp.group_size)
    if out_dim % m or in_dim % 8:
        return None
    bw = bstc.encode_weight(
        q_np.T.astype(np.int8), scale_np, m=m,
        threshold=float(cfg.mcbp.bstc_threshold),
    )
    coded = math.ceil(bw.encoded_bits / 8) + 4.0 * out_dim  # + f32 scales
    col_sparsity = [
        None if e is None else 1.0 - float(e.nnz.sum()) / e.bitmap.size
        for e in bw.encoded
    ]
    modeled = roofline.bstc_weight_traffic(
        in_dim, out_dim, m=m, nbits=bw.nbits, col_sparsity=col_sparsity,
        dtype_bytes=_dtype_bytes(cfg),
    )["bstc_bytes"]
    return float(coded), float(modeled)


def _kernel_roundtrip_check(q_np: np.ndarray, scale_np: np.ndarray,
                            cfg) -> None:
    """Round-trip ONE matrix through the ``bstc_matmul`` kernel family's
    compressed operands and assert the lossless reconstruction — pins the
    served values to the actual BSTC code path (the dense-reconstruction
    law, exercised on the real weights rather than assumed)."""
    from repro.kernels.bstc_matmul.ops import (
        prepare_bstc_matmul_operands, reconstruct_dense_weight,
    )

    in_dim, out_dim = q_np.shape
    m = int(cfg.mcbp.group_size)
    if out_dim % m or in_dim % 8:
        return
    ops = prepare_bstc_matmul_operands(
        q_np.T.astype(np.int8), scale_np, m=m, tile_k=in_dim,
        threshold=float(cfg.mcbp.bstc_threshold),
    )
    rebuilt = np.asarray(reconstruct_dense_weight(ops)).astype(np.int8)
    if not np.array_equal(rebuilt, q_np.T.astype(np.int8)):
        raise AssertionError(
            "BSTC round-trip mismatch: reconstruct_dense_weight did not "
            "recover the int8 weight — the coded layout cannot serve"
        )


def prepare_serve_params(params: Tree, cfg, layout,
                         fmt: Optional[str] = None) -> Tuple[Tree, WeightPlan]:
    """Convert decode-time projection leaves for ``fmt`` and price them.

    Returns ``(serve_params, plan)``.  ``fmt=None`` resolves from the
    config/env.  ``"bf16"`` returns the params object UNTOUCHED (the
    default path stays bit-for-bit) with a plan priced at raw-dtype bytes.
    ``"int8"``/``"bstc"`` replace each projection with a quantized record
    (elementwise jnp math, so sharded inputs keep their placement); tied
    embeddings get an explicit ``lm_head`` record derived from
    ``embed.T``, matching the engine's tied head read.  ``"bstc"`` serves
    the SAME records (lossless coding) but prices the measured coded
    layout, round-tripping the first matrix through the kernel operands.
    """
    fmt = resolve(cfg) if fmt is None else fmt
    if fmt not in FORMATS:
        raise ValueError(
            f"weight_format={fmt!r} is not one of {FORMATS} (config "
            f"mcbp.weight_format or ${ENV_VAR})"
        )
    dt = _dtype_bytes(cfg)
    entries: List[WeightEntry] = []
    tied_head = fmt != "bf16" and "lm_head" not in params \
        and "embed" in params
    serve = params
    checked_roundtrip = False

    for path, proj in projection_paths(params):
        w = get_path(params, path)
        shape = tuple(w.shape)
        copies = int(np.prod(shape[:-2], dtype=np.int64)) if len(shape) > 2 else 1
        in_dim, out_dim = int(shape[-2]), int(shape[-1])
        bf16_b = float(dt * in_dim * out_dim * copies)
        int8_b = float((in_dim * out_dim + 4 * out_dim) * copies)
        if fmt == "bf16":
            entries.append(WeightEntry(
                path="/".join(path), proj=proj, copies=copies,
                in_dim=in_dim, out_dim=out_dim,
                coded_bytes=bf16_b, int8_bytes=int8_b, bf16_bytes=bf16_b,
                modeled_bytes=bf16_b,
            ))
            continue
        rec = quantize(w)
        serve = _set(serve, path, rec)
        coded_b, modeled_b, fell_back = int8_b, int8_b, False
        if fmt == "bstc":
            q_np = np.asarray(rec["q"]).reshape(copies, in_dim, out_dim)
            s_np = np.asarray(rec["scale"]).reshape(copies, out_dim)
            coded_b, modeled_b = 0.0, 0.0
            for c in range(copies):
                mb = _bstc_matrix_bytes(q_np[c], s_np[c], cfg)
                if mb is None:
                    coded_b += int8_b / copies
                    modeled_b += int8_b / copies
                    fell_back = True
                    continue
                coded_b += mb[0]
                modeled_b += mb[1]
                if not checked_roundtrip:
                    _kernel_roundtrip_check(q_np[c], s_np[c], cfg)
                    checked_roundtrip = True
        entries.append(WeightEntry(
            path="/".join(path), proj=proj, copies=copies,
            in_dim=in_dim, out_dim=out_dim,
            coded_bytes=float(coded_b), int8_bytes=int8_b,
            bf16_bytes=bf16_b, modeled_bytes=float(modeled_b),
            bstc_fallback=fell_back,
        ))

    # tied embeddings: the engine reads embed.T as the head — price it in
    # every format, and materialize a record for it on the quantized paths
    if "lm_head" not in params and "embed" in params:
        V, D = (int(s) for s in params["embed"].shape)
        bf16_b = float(dt * V * D)
        int8_b = float(V * D + 4 * V)
        if fmt == "bf16":
            entries.append(WeightEntry(
                path="embed.T", proj="lm_head", copies=1, in_dim=D,
                out_dim=V, coded_bytes=bf16_b,
                int8_bytes=int8_b, bf16_bytes=bf16_b, modeled_bytes=bf16_b,
            ))
        elif tied_head:
            head = jnp.swapaxes(jnp.asarray(params["embed"]), -1, -2)
            rec = quantize(head)
            serve = _set(serve, ("lm_head",), rec)
            coded_b, modeled_b, fell_back = int8_b, int8_b, False
            if fmt == "bstc":
                mb = _bstc_matrix_bytes(
                    np.asarray(rec["q"]), np.asarray(rec["scale"]), cfg
                )
                if mb is not None:
                    coded_b, modeled_b = mb
                else:
                    fell_back = True
            entries.append(WeightEntry(
                path="embed.T", proj="lm_head", copies=1, in_dim=D,
                out_dim=V, coded_bytes=float(coded_b),
                int8_bytes=int8_b, bf16_bytes=bf16_b,
                modeled_bytes=float(modeled_b), bstc_fallback=fell_back,
            ))

    return serve, WeightPlan(fmt=fmt, entries=entries)
