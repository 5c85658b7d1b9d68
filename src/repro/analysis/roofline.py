"""Roofline analysis from compiled dry-run artifacts (no real hardware).

Three terms per (arch × shape × mesh), in seconds:

  compute    = device_FLOPs / peak_FLOPs
  memory     = device_bytes / HBM_bw
  collective = Σ collective operand bytes / link_bw

Sources: ``compiled.cost_analysis()`` yields per-device (post-SPMD) flops
and bytes; collective bytes are parsed from ``compiled.as_text()`` by
summing operand sizes of all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute ops (the partitioned module's shapes are
already per-device).  Hardware constants are the v5e targets given in the
brief.
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, Optional

# --- hardware model (TPU v5e targets from the brief) ----------------------


@dataclasses.dataclass(frozen=True)
class HW:
    peak_flops: float = 197e12  # bf16 FLOP/s per chip
    hbm_bw: float = 819e9  # bytes/s per chip
    ici_bw: float = 50e9  # bytes/s per link (~)
    hbm_per_chip: float = 16e9  # v5e HBM capacity


V5E = HW()

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
    "collective-broadcast",
    "ragged-all-to-all",
)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b(pred|bf16|f16|f32|f64|s4|u4|s8|u8|s16|u16|s32|u32|s64|u64|c64|c128)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, float]:
    """Operand bytes of every collective, by kind, × while-loop trip counts.

    Thin wrapper over :class:`repro.analysis.hlo.HloModule` (which resolves
    operand shapes by name and loop multipliers from condition constants /
    known_trip_count annotations).
    """
    from repro.analysis.hlo import HloModule

    return HloModule(hlo_text).collective_bytes()


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    device_flops: float
    device_bytes: float
    collective_bytes: float
    collective_by_kind: Dict[str, float]
    model_flops: float  # analytic "useful" flops (global)
    peak_memory_bytes: Optional[float]
    xla_flops: float = 0.0  # cost_analysis (loop bodies counted once)
    xla_bytes: float = 0.0
    loop_mults: Optional[Dict[str, float]] = None
    # measured serve-time weight traffic per decode step (the scheduler's
    # weight_read counter / WeightPlan total; 0.0 when not supplied)
    weight_read_bytes: float = 0.0

    hw: HW = V5E

    # --- the three terms (seconds) -----------------------------------
    @property
    def t_compute(self) -> float:
        return self.device_flops / self.hw.peak_flops

    @property
    def t_memory(self) -> float:
        return self.device_bytes / self.hw.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / self.hw.ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (device_flops × chips): remat/redundancy waste."""
        total = self.device_flops * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """How much of the bound time is useful compute — the perf score.

        = (model_flops/chips/peak) / max(term): 1.0 means the dominant
        roofline term is fully useful compute.
        """
        t_useful = self.model_flops / self.chips / self.hw.peak_flops
        return t_useful / self.t_bound if self.t_bound else 0.0

    def to_dict(self) -> Dict:
        return {
            "arch": self.arch,
            "shape": self.shape,
            "mesh": self.mesh,
            "chips": self.chips,
            "device_flops": self.device_flops,
            "device_bytes": self.device_bytes,
            "collective_bytes": self.collective_bytes,
            "collective_by_kind": self.collective_by_kind,
            "model_flops": self.model_flops,
            "peak_memory_bytes": self.peak_memory_bytes,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "xla_flops": self.xla_flops,
            "xla_bytes": self.xla_bytes,
            "loop_mults": self.loop_mults,
            "weight_read_bytes": self.weight_read_bytes,
        }


def bgpp_kernel_traffic(
    S: int, D: int, rounds: int = 4, keep_ratio: float = 0.25, nbits: int = 7
) -> Dict[str, float]:
    """Analytic per-(query, kv-head) HBM bytes of the BGPP *kernel* path.

    The jnp fallback in the serving engine materializes unpacked bit planes
    (8× blow-up) and is slower than dense int8 — exactly mirroring the
    paper's own GPU result (Fig. 20: software-only MCBP = 1.03×).  The
    validated Pallas kernel (``repro.kernels.bgpp_score``) consumes the
    packed planes in VMEM; its traffic is structurally determined:

      sign plane (once)      S · D/8
      round r plane          k_r · D/8,   k_0 = S, k_r = max(k_max, S/2^r)
      formal compute         k_max · (nbits·D/8 + D/8 + D + 8)
                             (re-fetch the survivor's packed planes + sign
                             to reconstruct K, read its int8 V row, two
                             f32 scales — the exact per-head row the
                             serving counter ``kv_cache._token_row_bytes``
                             prices, so measured/modeled gates at ~1.0)

    vs the dense int8 baseline 2·S·D (K+V).  The f32 output write is NOT
    part of ``bgpp_kernel_bytes`` (the cache counter never charges it);
    it is reported separately as ``output_write_bytes``.  Returns bytes +
    the ratio.
    """
    # ceil, matching THE serving plan (repro.serving.kv_cache
    # .bgpp_decode_plan) so measured-vs-modeled comparisons never carry a
    # silent rounding mismatch in k_max
    k_max = max(1, math.ceil(S * keep_ratio))
    bytes_ = S * D / 8.0  # sign
    k_r = S
    for r in range(rounds):
        bytes_ += k_r * D / 8.0
        k_r = max(k_max, S >> (r + 1))
    bytes_ += k_max * (nbits * D / 8.0 + D / 8.0 + D + 8)
    dense = 2.0 * S * D
    return {
        "bgpp_kernel_bytes": bytes_,
        "dense_int8_bytes": dense,
        "reduction": dense / bytes_,
        "k_max": k_max,
        "output_write_bytes": D * 4.0,
    }


def bstc_weight_traffic(
    in_dim: int,
    out_dim: int,
    m: int = 4,
    nbits: int = 7,
    col_sparsity=None,
    dtype_bytes: int = 2,
) -> Dict[str, float]:
    """Closed-form serve-time HBM bytes of ONE ``(in, out)`` projection
    under the BSTC two-state weight coding (paper §4.1).

    Per magnitude plane ``p`` with ``m``-bit column sparsity ``sc_p`` the
    coded stream is ``in·out / CR(m, sc_p)`` bits
    (:func:`repro.core.bstc.compression_ratio_closed_form`); the sign
    plane is always raw (``in·out`` bits) and the f32 output-channel
    scales add ``4·out`` bytes.  ``col_sparsity`` is a per-plane sequence
    — ``None`` entries mean the encoder kept that plane raw (sparsity
    below threshold or coding would not shrink it), matching
    ``encode_weight``'s per-plane decision, so feeding the MEASURED column
    sparsities reproduces the measured stream to within byte rounding
    (the ±10% reconciliation gate in the serving bench rides on this).
    Omitting ``col_sparsity`` prices every plane raw — plain int8.

    Returns coded bytes plus the int8/bf16 baselines and reductions.
    """
    from repro.core.bstc import compression_ratio_closed_form

    if col_sparsity is None:
        col_sparsity = [None] * nbits
    if len(col_sparsity) != nbits:
        raise ValueError(
            f"col_sparsity has {len(col_sparsity)} entries, expected "
            f"nbits={nbits}"
        )
    n = float(in_dim) * float(out_dim)
    bits = n  # sign plane, always raw
    for sc in col_sparsity:
        if sc is None:
            bits += n
        else:
            bits += n / compression_ratio_closed_form(m, float(sc))
    coded = bits / 8.0 + 4.0 * out_dim
    int8 = n + 4.0 * out_dim
    bf16 = dtype_bytes * n
    return {
        "bstc_bytes": coded,
        "int8_bytes": int8,
        "bf16_bytes": bf16,
        "reduction_vs_int8": int8 / coded,
        "reduction_vs_bf16": bf16 / coded,
    }


def model_flops_for(cfg, shape) -> float:
    """Analytic MODEL_FLOPS for the cell (global, per step).

    train: 6·N·D (fwd+bwd); prefill: 2·N·D; decode: 2·N_active per token ×
    batch (+ attention KV term for decode, which dominates long contexts).
    """
    n_active = cfg.active_params()
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        return 6.0 * n_active * B * S
    if shape.kind == "prefill":
        return 2.0 * n_active * B * S
    # decode: one token; include the KV-attention matvec flops
    attn_layers = sum(
        1 for i in range(cfg.num_layers) if cfg.layer_is_attention(i)
    )
    kv_flops = 0.0
    for i in range(cfg.num_layers):
        if not cfg.layer_is_attention(i):
            continue
        kind, w = cfg.layer_attn_window(i)
        span = min(S, w) if (kind in ("sliding", "chunked") and w > 0) else S
        kv_flops += 2.0 * 2.0 * cfg.num_heads * cfg.head_dim * span  # QK^T + PV
    return (2.0 * n_active + kv_flops) * B


def roofline_from_compiled(
    compiled, arch: str, shape, mesh_name: str, chips: int, cfg
) -> RooflineReport:
    """Three-term roofline from the compiled artifact.

    The text-level HLO model (``repro.analysis.hlo``) supplies the terms
    because XLA's ``cost_analysis()`` visits each while (scan) body once —
    a ~num_layers× undercount on the train/prefill graphs.  The text model
    multiplies loop bodies by their recovered trip counts; it matches
    cost_analysis on loop-free decode graphs (validated in tests).  XLA's
    numbers are retained in the report for reference.
    """
    from repro.analysis.hlo import HloModule

    cost = compiled.cost_analysis() or {}
    xla_flops = float(cost.get("flops", 0.0))
    xla_bytes = float(cost.get("bytes accessed", 0.0))
    mod = HloModule(compiled.as_text())
    flops = mod.dot_flops()
    bytes_ = mod.traffic_bytes()
    coll = mod.collective_bytes()
    try:
        mem = compiled.memory_analysis()
        peak = float(
            getattr(mem, "temp_size_in_bytes", 0)
            + getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
            - getattr(mem, "alias_size_in_bytes", 0)
        )
    except Exception:  # pragma: no cover - backend-dependent
        peak = None
    return RooflineReport(
        arch=arch,
        shape=shape.name,
        mesh=mesh_name,
        chips=chips,
        device_flops=max(flops, xla_flops),
        device_bytes=bytes_,
        collective_bytes=coll["total"],
        collective_by_kind={k: v for k, v in coll.items() if v and k != "total"},
        model_flops=model_flops_for(cfg, shape),
        peak_memory_bytes=peak,
        xla_flops=xla_flops,
        xla_bytes=xla_bytes,
        loop_mults=mod.while_summary(),
    )
