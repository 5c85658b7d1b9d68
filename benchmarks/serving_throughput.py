"""Serving throughput: chunked vs eager admission vs lockstep decode under a
Poisson-ish arrival trace, for the three KV formats (bf16 / int8 / bgpp),
plus the paged KV cache under a shared-system-prompt trace.

    PYTHONPATH=src python benchmarks/serving_throughput.py \\
        [--arch phi4-mini-3.8b] [--slots 2] [--requests 6] [--seed 0] \\
        [--kv-formats bf16,int8,bgpp] [--chunk-budget 8] [--quick] \\
        [--server-sim] \\
        [--page-size 8] [--shared-prefix 16] \\
        [--bgpp-rounds 4] [--bgpp-keep-ratio 0.25] [--mesh 2,4] \\
        [--decode-kernel auto|jnp|interpret|kernel] \\
        [--weight-format bf16|int8|bstc] \\
        [--baseline BENCH_serving.json] [--out BENCH_serving.json]

All runtimes drive the SAME jitted serve_step and the same seeded request
trace (staggered arrivals, varying prompt lengths and decode budgets):

  chunked  — the production scheduler: bucketed fixed-shape prefill chunks
             (jitted once per bucket, cache donated) interleaved with the
             batched decode step, at most --chunk-budget prefill tokens
             between consecutive decode steps.
  eager    — the PR-2 baseline: whole-prompt B=1 admission the moment a
             slot frees up; decode stalls for the full prefill.
  lockstep — the pre-ISSUE-2 baseline: groups of ``slots`` requests padded
             to a common length, prefilled together, decoded until the
             LONGEST budget in the group finishes.

Reported per (format, runtime): tokens/s (useful tokens only), mean busy
occupancy (slots holding an admitted request — PREFILLING or DECODING —
over total slots: a reserved row is occupied capacity even while its
prompt waits its turn to chunk), TTFT and ITL p50/p95, per-request queue
waits, and ``decode_kv_bytes_per_step`` — the KV bytes one batched decode
step gathers (``Scheduler.stats()["kv_read"]``).  The bgpp format decodes
two-phase (bit-plane prediction + top-``--bgpp-keep-ratio`` full-precision
gather, ``--bgpp-rounds`` progressive rounds), so its bytes-read must land
WELL under the bf16 row — that ordering is part of the gate.  Runs on CPU
via interpret-mode kernel dispatch (auto-detected off-TPU).  CSV on stdout
per the benchmark contract; ``--out`` writes the JSON consumed as the
BENCH_serving baseline.

``--weight-format`` flips the serve-time WEIGHT path (the knob
``repro.serving.weights`` resolves once per built step): every scheduler
row then carries ``weight_format`` / ``decode_weight_bytes_per_step``
columns from ``stats()["weight_read"]``, and the baseline gains a
``weight_read`` section pricing all three formats statically.  Two weight
gates run in EVERY invocation including ``--quick``: bstc coded bytes
must be <= bf16/2, and the measured coded stream must reconcile with the
closed-form model (``roofline.bstc_weight_traffic``) at 1.0 +- 10%.

``--mesh DATA,MODEL`` runs every scheduler sharded over a device mesh (KV
pools heads-parallel on ``model``, slots on ``data``; needs data*model
visible devices) and the emitted rows gain per-device and interconnect
kv-bytes columns.  With or without the flag, each format's baseline entry
carries ``kv_read_mesh`` — the static per-mesh decode-read pricing for
1x1 / 2x1 / 1x4 / 2x4 (total, per-device share, tensor-parallel
all-reduces + paged write-broadcast interconnect) — plus a
``sharded_smoke`` section pinning the single-device occupancy the CI
meshed launcher smoke is gated on.

  paged    — the chunked scheduler on the paged KV layout (pooled pages +
             page table + hash-based prefix reuse), driven by a trace whose
             requests share a ``--shared-prefix``-token system prompt.
             Reports prefix-hit rate and peak resident KV bytes next to the
             slot layout's dense allocation for the same traffic.

Full (non ``--quick``) runs also emit a ``serving_<fmt>_spec`` row: the
SAME chunked trace re-run with bit-plane speculative decoding on
(``spec_decode=True``, the config's gamma/planes).  The row carries the
acceptance economics — ``accepted_tokens_per_step`` (accepted tokens per
*physical* serve_step, draft + verify) and the per-accepted-token
kv/weight byte prices — and the run fails if the speculative trace's
generated tokens differ from the chunked row's in a single position
(speculation may only move wall clock, never tokens).

``--server-sim`` additionally replays the trace through the asyncio front
door (``repro.serving.server.simulate_clients``: tiered rotating clients,
every 3rd disconnecting after one token) on the paged layout and emits an
informational ``serving_<fmt>_server`` row — cancels, sheds, preemptions,
per-tier ITL.  The row is never gated against baselines (its wall clock
includes event-loop overhead), but the per-step page-leak check is armed
and a non-empty pool at the end fails the run.

``--quick`` runs one format with chunked+eager only and exits nonzero if
chunked admission shows lower occupancy than eager OR a worse decode-tail
ITL p95 (the stall chunking exists to remove) — the CI regression gate
for the admission path.  ``--baseline`` (usually the committed
BENCH_serving.json) tightens the gate against the recorded numbers with
stated tolerances: chunked occupancy may not drop more than
``OCC_TOLERANCE`` (absolute — occupancy is step-deterministic), and the
chunked/eager decode-tail ITL p95 *ratio* may not exceed the baseline's
ratio by more than ``ITL_RATIO_FACTOR``x (a ratio, so CI-runner speed
cancels out).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

try:  # python -m benchmarks.serving_throughput
    from benchmarks.common import emit, emit_header
except ImportError:  # python benchmarks/serving_throughput.py
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from common import emit, emit_header

from repro.configs import (  # noqa: E402
    ARCH_REGISTRY, WEIGHT_FORMATS, apply_bgpp_overrides,
    apply_decode_kernel_override, apply_weight_format_override, get_config,
)
from repro.models import model_zoo  # noqa: E402
from repro.serving import engine, kernel_decode, kv_cache as kvc  # noqa: E402
from repro.serving import sharded as shd  # noqa: E402
from repro.serving import weights as swt  # noqa: E402
from repro.serving.request import poisson_trace  # noqa: E402
from repro.serving.scheduler import Scheduler  # noqa: E402


# stated regression-gate tolerances (--baseline):
OCC_TOLERANCE = 0.02  # absolute mean-occupancy drop allowed vs baseline
ITL_RATIO_FACTOR = 4.0  # chunked/eager itl_p95 ratio growth allowed

# mesh points priced in every baseline (static — the kv-read counter IS the
# gather plan, so no devices are needed to price a mesh shape)
MESH_POINTS = ((1, 1), (2, 1), (1, 4), (2, 4))


def mesh_kv_entries(layout, cfg, params):
    """Per-mesh decode-read breakdown: total, per-device share, and the
    interconnect bytes (tensor-parallel all-reduces + paged write
    broadcast) a sharded serve_step moves per decode step."""
    out = {}
    specs = model_zoo.param_specs(cfg)
    for d, m in MESH_POINTS:
        r = kvc.decode_read_bytes(
            layout, cfg, (d, m), shd.projection_shards(params, specs, (d, m)))
        out[f"{d}x{m}"] = {
            "decode_bytes_per_step": round(r["total"]),
            "per_device_bytes_per_step": round(r["per_device"]["total"]),
            "kv_shards": r["per_device"]["shards"],
            "interconnect": {k: round(v)
                             for k, v in r["interconnect"].items()},
        }
    return out


def run_scheduler(params, cfg, layout, reqs, admission, chunk_budget,
                  shared=None, rules=None, sched_kw=None, sink=None):
    kw = {} if rules is None else {"rules": rules}
    kw |= sched_kw or {}
    sched = Scheduler(params, cfg, layout, admission=admission,
                      chunk_budget=chunk_budget,
                      prefill_kw=dict(block_q=16, block_k=32),
                      shared_fns=shared, **kw)
    for r in reqs:
        sched.submit(r)
    t0 = time.perf_counter()
    sched.run(max_steps=10_000)
    wall = time.perf_counter() - t0
    stats = sched.stats(wall)
    out = {
        "tokens_per_s": stats["tokens_per_s"],
        "mean_occupancy": stats["mean_occupancy"],
        "decoded_tokens": stats["decoded_tokens"],
        "wall_s": stats["wall_s"],
        "ttft_s_p50": stats["ttft_s"]["p50"],
        "ttft_s_p95": stats["ttft_s"]["p95"],
        "itl_s_p50": stats["itl_s"]["p50"],
        "itl_s_p95": stats["itl_s"]["p95"],
        "max_prefill_tokens_per_step": stats["max_prefill_tokens_per_step"],
        "mean_queue_wait_steps": float(np.mean(
            [r["queue_wait_steps"] for r in stats["requests"]])),
    }
    kv = stats["kv_read"]
    out |= {
        "decode_kv_bytes_per_step": kv["decode_bytes_per_step"],
        "decode_kv_bytes_reduction_vs_bf16":
            kv["decode_bytes_reduction_vs_bf16"],
        "kv_shards": kv["kv_shards"],
        "decode_kv_bytes_per_device_per_step":
            kv["decode_bytes_per_device_per_step"],
        "interconnect_bytes_per_step": kv["interconnect_bytes_per_step"],
        "interconnect_bytes": kv["interconnect_bytes"],
    }
    wr = stats["weight_read"]
    out |= {
        "weight_format": wr["weight_format"],
        "decode_weight_bytes_per_step": wr["decode_bytes_per_step"],
        "decode_weight_bytes_reduction_vs_bf16":
            wr["decode_bytes_reduction_vs_bf16"],
        "weight_measured_over_modeled": wr["measured_over_modeled"],
        "decode_weight_bytes_per_device_per_step":
            wr["decode_bytes_per_device_per_step"],
    }
    if "bgpp" in kv:
        out["bgpp_full_rows_per_slot"] = kv["bgpp"]["full_rows_per_slot"]
    if "paged" in stats:
        pg = stats["paged"]
        out |= {
            "prefix_hit_rate": pg["prefix_hit_rate"],
            "prefix_hit_tokens": pg["prefix_hit_tokens"],
            "resident_kv_bytes_peak": pg["resident_kv_bytes_peak"],
            "slot_resident_kv_bytes": pg["slot_resident_kv_bytes"],
        }
    if "spec" in stats:
        sp = stats["spec"]
        out |= {
            "spec_gamma": sp["gamma"],
            "spec_draft_planes": sp["draft_planes"],
            "accepted_tokens_per_step": sp["accepted_tokens_per_step"],
            "accepted_tokens_per_round": sp["accepted_tokens_per_round"],
            "draft_hit_rate": sp["draft_hit_rate"],
            "kv_bytes_per_accepted_token": sp["kv_bytes_per_accepted_token"],
            "weight_bytes_per_accepted_token":
                sp["weight_bytes_per_accepted_token"],
            "modeled_weight_bytes_per_accepted_token":
                sp["modeled_weight_bytes_per_accepted_token"],
        }
    if sink is not None:
        sink["generated"] = {r.rid: [int(t) for t in r.generated]
                             for r in sched.finished}
    return out, sched.shared_fns()


def run_lockstep(params, cfg, layout, reqs, serve_step=None):
    """Fixed-budget group decode (the old launch/serve.py skeleton): pad a
    group to one width, prefill together, decode until the group's longest
    budget; admission only at group boundaries."""
    if serve_step is None:
        serve_step = jax.jit(engine.make_serve_step(cfg, layout))
    slots = layout.batch
    queue = list(reqs)
    step_now = 0
    occupancy, decoded, waits = [], 0, []
    t0 = time.perf_counter()
    while queue:
        arrived = [r for r in queue if r.arrival_step <= step_now]
        if not arrived:  # idle until the next arrival (no device work)
            step_now = min(r.arrival_step for r in queue)
            continue
        group = arrived[:slots]
        queue = [r for r in queue if r not in group]
        waits.extend(step_now - r.arrival_step for r in group)
        width = max(r.prompt_len for r in group)
        prompts = jnp.stack([
            jnp.pad(jnp.asarray(r.prompt), (width - r.prompt_len, 0))
            for r in group
        ])
        if len(group) < slots:
            prompts = jnp.pad(prompts, ((0, slots - len(group)), (0, 0)))
        logits, cache = engine.prefill(params, cfg, layout, prompts,
                                       block_q=16, block_k=32)
        cur = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        # prefill samples token 1; the group decodes until its longest
        # budget even though shorter requests finished — the lockstep waste
        T = max(r.max_new_tokens for r in group) - 1
        T = min(T, layout.max_seq - width)
        for t in range(T):
            live = sum(1 for r in group if t < r.max_new_tokens - 1)
            occupancy.append(live / slots)
            decoded += live
            logits, cache = serve_step(params, cache, cur)
            cur = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        step_now += T
    wall = time.perf_counter() - t0
    return {
        "tokens_per_s": round(decoded / wall, 2) if wall > 0 else None,
        "mean_occupancy": float(np.mean(occupancy)) if occupancy else 0.0,
        "decoded_tokens": decoded,
        "wall_s": round(wall, 3),
        "mean_queue_wait_steps": float(np.mean(waits)) if waits else 0.0,
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="phi4-mini-3.8b",
                    choices=sorted(ARCH_REGISTRY))
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--chunk-budget", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kv-formats", default="bf16,int8,bgpp")
    ap.add_argument("--page-size", type=int, default=8,
                    help="tokens per KV page for the paged runtime")
    ap.add_argument("--shared-prefix", type=int, default=16,
                    help="shared system-prompt tokens in the paged trace")
    ap.add_argument("--bgpp-rounds", type=int, default=4,
                    help="bgpp progressive-prediction rounds")
    ap.add_argument("--bgpp-keep-ratio", type=float, default=0.25,
                    help="fraction of keys the bgpp decode fetches at "
                         "full precision")
    ap.add_argument("--decode-kernel", default=None,
                    choices=sorted(kernel_decode.MODES),
                    help="global-layer decode attend routing (auto = "
                         "compiled Pallas kernel on TPU, legacy jnp "
                         "elsewhere); every serving row carries the "
                         "resolved mode as a decode_kernel column")
    ap.add_argument("--weight-format", default=None,
                    choices=sorted(WEIGHT_FORMATS),
                    help="serve-time weight numerics for the decode "
                         "projections (bf16 = raw leaves, bit-for-bit; "
                         "int8/bstc = quantized records priced by the "
                         "weight_read counter)")
    ap.add_argument("--quick", action="store_true",
                    help="one format, chunked+eager only — the CI gate")
    ap.add_argument("--server-sim", action="store_true",
                    help="also replay the trace through the asyncio front "
                         "door (tiered clients, every 3rd disconnecting) "
                         "on the paged layout: an informational "
                         "serving_<fmt>_server row, never baseline-gated, "
                         "but the per-step page-leak check is armed and "
                         "the pool must drain")
    ap.add_argument("--baseline", default=None,
                    help="committed BENCH JSON to gate regressions against "
                         f"(occupancy -{OCC_TOLERANCE} absolute, itl-p95 "
                         f"ratio x{ITL_RATIO_FACTOR})")
    ap.add_argument("--out", default=None,
                    help="write the JSON baseline (e.g. BENCH_serving.json)")
    ap.add_argument("--mesh", default=None,
                    help="DATA,MODEL mesh shape (e.g. 2,4): run the "
                         "schedulers sharded over a device mesh (needs "
                         "data*model devices; on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8).  "
                         "Static per-mesh kv-read entries are priced in "
                         "the baseline regardless of this flag")
    args = ap.parse_args()
    rules = None
    if args.mesh:
        mesh_dm = shd.parse_mesh_arg(args.mesh)
        rules = shd.rules_for(*mesh_dm)

    cfg = apply_bgpp_overrides(
        get_config(args.arch, smoke=True),
        rounds=args.bgpp_rounds, keep_ratio=args.bgpp_keep_ratio,
    )
    cfg = apply_decode_kernel_override(cfg, args.decode_kernel)
    cfg = apply_weight_format_override(cfg, args.weight_format)
    dk_mode = kernel_decode.resolve(cfg)
    wf_mode = swt.resolve(cfg)
    params, _ = model_zoo.init(jax.random.key(0), cfg)
    formats = args.kv_formats.split(",")
    if args.quick:
        # one format, but the SAME trace parameters as the full run, so the
        # --baseline gate compares like for like
        formats = formats[:1]

    results = {"config": vars(args) | {"arch_resolved": cfg.name}}
    emit_header()
    ok = True
    for fmt in formats:
        layout = kvc.layout_for(cfg, args.slots, args.max_seq, kv_format=fmt)
        entry = {"decode_kernel": dk_mode, "weight_format": wf_mode,
                 "kv_read_mesh": mesh_kv_entries(layout, cfg, params)}
        shared = None
        chunk_sink = {}
        runtimes = ["chunked", "eager"] + ([] if args.quick else ["lockstep"])
        for runtime in runtimes:
            rng = np.random.default_rng(args.seed)  # identical trace
            reqs = poisson_trace(rng, args.requests, cfg.vocab_size,
                                 args.max_new, arrival_rate=3.0,
                                 min_new=max(2, args.max_new // 3),
                                 max_prompt=min(23, args.max_seq - 2))
            if runtime == "lockstep":
                # lockstep prefills an unsharded cache itself, so never
                # feed it a mesh-jitted serve_step
                entry[runtime] = run_lockstep(
                    params, cfg, layout, reqs,
                    serve_step=shared["serve_step"]
                    if shared and rules is None else None,
                )
            else:
                entry[runtime], shared = run_scheduler(
                    params, cfg, layout, reqs, runtime, args.chunk_budget,
                    shared=shared, rules=rules,
                    sink=chunk_sink if runtime == "chunked" else None,
                )
            r = entry[runtime]
            us = 1e6 / r["tokens_per_s"] if r["tokens_per_s"] else 0.0
            extra = ""
            if runtime != "lockstep":
                extra = (f";ttft_p95={r['ttft_s_p95']}"
                         f";itl_p95={r['itl_s_p95']}"
                         f";kv_step={r['decode_kv_bytes_per_step']}"
                         f";weight_format={r['weight_format']}"
                         f";w_step={r['decode_weight_bytes_per_step']}")
                if rules is not None:
                    extra += (f";kv_dev={r['decode_kv_bytes_per_device_per_step']}"
                              f";ic_step={r['interconnect_bytes_per_step']}")
            emit(f"serving_{fmt}_{runtime}", us,
                 f"occ={r['mean_occupancy']:.3f};tok_s={r['tokens_per_s']}"
                 f";decode_kernel={dk_mode}" + extra)
        delta = entry["chunked"]["mean_occupancy"] \
            - entry["eager"]["mean_occupancy"]
        entry["chunked_vs_eager_occupancy"] = round(delta, 4)
        itl_c = entry["chunked"]["itl_s_p95"]
        itl_e = entry["eager"]["itl_s_p95"]
        if itl_c is not None and itl_e is not None and itl_c > itl_e:
            # chunking exists to bound the decode-tail stall; a p95 ITL
            # regression against eager admission fails the gate even if
            # occupancy still reads fine
            ok = False
        if "lockstep" in entry:
            gain = entry["eager"]["mean_occupancy"] \
                - entry["lockstep"]["mean_occupancy"]
            entry["occupancy_gain"] = round(gain, 4)
        results[fmt] = entry
        print(f"# {fmt}: chunked occupancy "
              f"{entry['chunked']['mean_occupancy']:.3f} vs eager "
              f"{entry['eager']['mean_occupancy']:.3f} "
              f"({'+' if delta >= 0 else ''}{delta:.3f})"
              + (f", eager vs lockstep "
                 f"{entry['lockstep']['mean_occupancy']:.3f}"
                 if "lockstep" in entry else ""))
        if delta < -1e-9:
            ok = False
        if "lockstep" in entry and entry["occupancy_gain"] <= 0:
            ok = False
        if rules is not None:
            # the live counter must agree with the static per-mesh pricing:
            # a mesh the pricing says moves interconnect bytes (model-axis
            # head shards) must report them from the actual run
            want_ic = entry["kv_read_mesh"][
                f"{mesh_dm[0]}x{mesh_dm[1]}"]["interconnect"]["total"]
            got_ic = entry["chunked"]["interconnect_bytes"]
            if (want_ic > 0) != (got_ic > 0):
                print(f"# REGRESSION {fmt}: static mesh pricing says "
                      f"{want_ic} interconnect B/step but the live run "
                      f"counted {got_ic} B total")
                ok = False

        if not args.quick and dk_mode == "jnp" and rules is None:
            # jnp-vs-kernel comparison row: the SAME chunked trace with the
            # decode attend routed through the Pallas kernel family in
            # interpret mode.  On CPU CI this is kernel EMULATION — the
            # wall clock is flagged and never gated; the row exists so the
            # baseline records both paths side by side (on TPU the compiled
            # row replaces it).
            cfg_k = apply_decode_kernel_override(cfg, "interpret")
            rng = np.random.default_rng(args.seed)
            kreqs = poisson_trace(rng, args.requests, cfg.vocab_size,
                                  args.max_new, arrival_rate=3.0,
                                  min_new=max(2, args.max_new // 3),
                                  max_prompt=min(23, args.max_seq - 2))
            entry["chunked_interpret"], _ = run_scheduler(
                params, cfg_k, layout, kreqs, "chunked", args.chunk_budget,
            )
            entry["chunked_interpret"]["note"] = (
                "decode_kernel=interpret on CPU: Pallas interpret-mode "
                "emulation wall clock, NOT TPU kernel time — parity/bytes "
                "columns transfer, us_per_call does not"
            )
            rk = entry["chunked_interpret"]
            us = 1e6 / rk["tokens_per_s"] if rk["tokens_per_s"] else 0.0
            emit(f"serving_{fmt}_chunked_interpret", us,
                 f"occ={rk['mean_occupancy']:.3f};tok_s={rk['tokens_per_s']}"
                 f";decode_kernel=interpret;flag=cpu_interpret_emulation"
                 f";kv_step={rk['decode_kv_bytes_per_step']}")
            # routing must not change WHAT the step gathers: the kv-read
            # counter prices the plan, not the executor
            if rk["decode_kv_bytes_per_step"]                     != entry["chunked"]["decode_kv_bytes_per_step"]:
                print(f"# REGRESSION {fmt}: kernel-routed decode reads "
                      f"{rk['decode_kv_bytes_per_step']} B/step vs jnp "
                      f"{entry['chunked']['decode_kv_bytes_per_step']}")
                ok = False

        if not args.quick:
            # paged layout under a shared-system-prompt trace: later
            # requests must adopt the resident prompt pages (hit rate > 0)
            # and the pool must stay under the slot layout's dense rows
            rng = np.random.default_rng(args.seed)
            p_max_prompt = min(23, args.max_seq - 2 - args.shared_prefix)
            assert p_max_prompt >= 1, "--shared-prefix leaves no prompt room"
            preqs = poisson_trace(rng, args.requests, cfg.vocab_size,
                                  args.max_new, arrival_rate=3.0,
                                  min_new=max(2, args.max_new // 3),
                                  max_prompt=p_max_prompt,
                                  shared_prefix=args.shared_prefix)
            layout_p = kvc.layout_for(cfg, args.slots, args.max_seq,
                                      kv_format=fmt, layout="paged",
                                      page_size=args.page_size)
            entry["paged"], _ = run_scheduler(
                params, cfg, layout_p, preqs, "chunked", args.chunk_budget,
                rules=rules,
            )
            entry["paged"]["kv_read_mesh"] = mesh_kv_entries(layout_p, cfg,
                                                             params)
            r = entry["paged"]
            us = 1e6 / r["tokens_per_s"] if r["tokens_per_s"] else 0.0
            emit(f"serving_{fmt}_paged", us,
                 f"occ={r['mean_occupancy']:.3f};tok_s={r['tokens_per_s']}"
                 f";decode_kernel={dk_mode}"
                 f";prefix_hit_rate={r['prefix_hit_rate']}"
                 f";resident_kv_peak={r['resident_kv_bytes_peak']}"
                 f";slot_resident={r['slot_resident_kv_bytes']}")
            print(f"# {fmt}: paged prefix hit rate "
                  f"{r['prefix_hit_rate']:.3f}, resident KV peak "
                  f"{r['resident_kv_bytes_peak']} B vs slot "
                  f"{r['slot_resident_kv_bytes']} B")
            if r["prefix_hit_rate"] <= 0:
                ok = False
            if r["resident_kv_bytes_peak"] >= r["slot_resident_kv_bytes"]:
                ok = False

        if not args.quick and not layout.local_layers:
            # speculative decoding over the SAME chunked trace (global-only
            # stacks — local ring layers overwrite what rollback needs):
            # wall clock may move, tokens may not.  The row carries the
            # acceptance economics next to the chunked baseline.
            rng = np.random.default_rng(args.seed)
            qreqs = poisson_trace(rng, args.requests, cfg.vocab_size,
                                  args.max_new, arrival_rate=3.0,
                                  min_new=max(2, args.max_new // 3),
                                  max_prompt=min(23, args.max_seq - 2))
            spec_sink = {}
            entry["spec"], _ = run_scheduler(
                params, cfg, layout, qreqs, "chunked", args.chunk_budget,
                shared=shared, rules=rules,
                sched_kw={"spec_decode": True}, sink=spec_sink,
            )
            r = entry["spec"]
            us = 1e6 / r["tokens_per_s"] if r["tokens_per_s"] else 0.0
            emit(f"serving_{fmt}_spec", us,
                 f"occ={r['mean_occupancy']:.3f};tok_s={r['tokens_per_s']}"
                 f";decode_kernel={dk_mode}"
                 f";gamma={r['spec_gamma']};planes={r['spec_draft_planes']}"
                 f";acc_step={r['accepted_tokens_per_step']}"
                 f";acc_round={r['accepted_tokens_per_round']}"
                 f";kv_per_accepted={r['kv_bytes_per_accepted_token']}"
                 f";w_per_accepted={r['weight_bytes_per_accepted_token']}")
            print(f"# {fmt}: spec accepted/step "
                  f"{r['accepted_tokens_per_step']:.3f} "
                  f"({r['accepted_tokens_per_round']:.2f}/round, draft hit "
                  f"rate {r['draft_hit_rate']:.2f}); kv "
                  f"{r['kv_bytes_per_accepted_token']} B and weight "
                  f"{r['weight_bytes_per_accepted_token']} B per accepted "
                  f"token")
            if spec_sink["generated"] != chunk_sink["generated"]:
                print(f"# REGRESSION {fmt}: speculative decode changed the "
                      f"generated tokens vs the chunked run")
                ok = False

    if args.server_sim:
        # the same trace replayed through the asyncio front door
        # (repro.serving.server.simulate_clients): tiered rotating clients,
        # every 3rd hanging up after one token.  The wall clock includes
        # event-loop overhead, so the row is informational — never gated
        # against baselines — but the per-step PageAllocator.check leak
        # gate is armed and the pool must end empty.
        from repro.serving.server import simulate_clients
        fmt = formats[0]
        slayout = kvc.layout_for(cfg, args.slots, args.max_seq,
                                 kv_format=fmt, layout="paged",
                                 page_size=args.page_size)
        rng = np.random.default_rng(args.seed)
        sreqs = poisson_trace(rng, args.requests, cfg.vocab_size,
                              args.max_new, arrival_rate=3.0,
                              min_new=max(2, args.max_new // 3),
                              max_prompt=min(23, args.max_seq - 2))
        ssched = Scheduler(params, cfg, slayout, admission="chunked",
                           chunk_budget=args.chunk_budget,
                           **({"rules": rules} if rules is not None else {}))
        t0 = time.perf_counter()
        sv = simulate_clients(ssched, sreqs)
        wall = time.perf_counter() - t0
        tok_s = round(sv["decoded_tokens"] / wall, 1) if wall else 0.0
        us = 1e6 / tok_s if tok_s else 0.0
        tiers = ";".join(
            f"{tier}_itl_p50={t['itl_s']['p50']}"
            for tier, t in sorted(sv["tiers"].items()))
        emit(f"serving_{fmt}_server", us,
             f"occ={sv['mean_occupancy']:.3f};tok_s={tok_s}"
             f";cancelled={sv['cancelled_requests']}"
             f";shed={sv['shed_requests']}"
             f";preemptions={sv['preemptions']}"
             f";pages_in_use={sv['paged']['pages_in_use']}"
             f";{tiers};flag=informational_not_gated")
        results[f"{fmt}_server"] = {
            "note": "async front door replay: informational, not gated",
            "tokens_per_s": tok_s,
            "mean_occupancy": sv["mean_occupancy"],
            "cancelled_requests": sv["cancelled_requests"],
            "shed_requests": sv["shed_requests"],
            "preemptions": sv["preemptions"],
            "tiers": sv["tiers"],
            "disconnects": sum(c["disconnected"] for c in sv["clients"]),
        }
        print(f"# {fmt}: server sim cancelled "
              f"{sv['cancelled_requests']}/{len(sreqs)}, preemptions "
              f"{sv['preemptions']}, pool drained "
              f"({sv['paged']['pages_in_use']} pages in use)")
        if sv["paged"]["pages_in_use"] != 0:
            print("# REGRESSION: server sim leaked pages")
            ok = False

    # the tentpole's bytes ordering: bgpp's two-phase decode (bit-planes +
    # top-k full rows) must read WELL under the dense bf16 row — at least
    # 2x at the default keep ratio (8x at rounds=4, keep=0.25).  Formats
    # not driven live (--quick trims to bf16) are priced from their static
    # layouts — identical numbers, since the counter IS the gather plan —
    # so this gate also fires in the --quick CI run.
    def _step_bytes(fmt):
        live = results.get(fmt, {}).get("chunked")
        if live is not None:
            return live["decode_kv_bytes_per_step"]
        return round(kvc.decode_read_bytes(
            kvc.layout_for(cfg, args.slots, args.max_seq, kv_format=fmt), cfg
        )["total"])

    b_bytes, f_bytes = _step_bytes("bgpp"), _step_bytes("bf16")
    print(f"# kv bytes/decode-step: bgpp {b_bytes} vs bf16 {f_bytes} "
          f"({f_bytes / b_bytes:.2f}x reduction)")
    if 2 * b_bytes > f_bytes:
        print("# REGRESSION: bgpp decode reads are not well under bf16's")
        ok = False

    # the weight-format mirror of the bgpp ordering gate (fires in --quick
    # too): every format priced statically from the same params — identical
    # to the live counter, since the plan IS the counter — then (1) BSTC
    # coded bytes <= bf16/2 and (2) the measured coded stream reconciles
    # with the closed-form model (roofline.bstc_weight_traffic on measured
    # per-plane column sparsities) at 1.0 +- 10%
    wlayout = kvc.layout_for(cfg, args.slots, args.max_seq,
                             kv_format=formats[0])
    weight_entry = {"weight_format": wf_mode}
    for wf in WEIGHT_FORMATS:
        _, plan = swt.prepare_serve_params(
            params, apply_weight_format_override(cfg, wf), wlayout, wf)
        wrd = plan.decode_read_bytes()
        weight_entry[wf] = {
            "decode_bytes_per_step": round(wrd["total"]),
            "modeled_bytes_per_step": round(wrd["modeled"]),
            "measured_over_modeled": round(wrd["total"] / wrd["modeled"], 4),
            "per_projection": {n: round(v)
                               for n, v in wrd["per_projection"].items()},
        }
    results["weight_read"] = weight_entry
    wb = weight_entry["bstc"]["decode_bytes_per_step"]
    wf16 = weight_entry["bf16"]["decode_bytes_per_step"]
    print(f"# weight bytes/decode-step: bstc {wb} vs bf16 {wf16} "
          f"({wf16 / wb:.2f}x reduction)")
    if 2 * wb > wf16:
        print("# REGRESSION: bstc coded weights are not <= bf16/2")
        ok = False
    mm = weight_entry["bstc"]["measured_over_modeled"]
    if not 0.9 <= mm <= 1.1:
        print(f"# REGRESSION: bstc measured/modeled weight bytes {mm} "
              f"outside 1.0 +- 10%")
        ok = False
    # the live schedulers ran with wf_mode: their counter must equal the
    # static pricing (weights are layout-independent)
    for fmt in formats:
        live = results[fmt]["chunked"]["decode_weight_bytes_per_step"]
        want = weight_entry[wf_mode]["decode_bytes_per_step"]
        if live != want:
            print(f"# REGRESSION {fmt}: live weight counter {live} B/step "
                  f"!= static {wf_mode} pricing {want}")
            ok = False

    if not args.quick:
        # committed single-device reference for the CI sharded-serving
        # launcher smoke: the exact trace launch/serve.py runs at
        # --arch deepseek-7b --mesh 2,4 (the smoke arch whose head counts
        # divide model=4).  Occupancy is host-side scheduling, so it is
        # mesh-invariant — CI pins the meshed launcher run to this number
        # within OCC_TOLERANCE — and the static 2x4 entry prices the
        # interconnect bytes that run must report as > 0.
        scfg = get_config("deepseek-7b", smoke=True)
        sparams, _ = model_zoo.init(jax.random.key(0), scfg)
        slayout = kvc.layout_for(scfg, 4, 128, kv_format="bf16")
        rng = np.random.default_rng(args.seed)
        sreqs = poisson_trace(rng, 4, scfg.vocab_size, 8, 2.0,
                              max_prompt=23)
        smoke, _ = run_scheduler(sparams, scfg, slayout, sreqs,
                                 "chunked", 16)
        results["sharded_smoke"] = {
            "arch": "deepseek-7b", "kv_format": "bf16", "kv_layout": "slot",
            "slots": 4, "requests": 4, "max_new": 8, "max_seq": 128,
            "chunk_budget": 16, "arrival_rate": 2.0, "seed": args.seed,
            "mean_occupancy": smoke["mean_occupancy"],
            "kv_read_mesh": mesh_kv_entries(slayout, scfg, sparams),
        }
        sm = results["sharded_smoke"]["kv_read_mesh"]["2x4"]
        print(f"# sharded_smoke (deepseek-7b, 4 slots, bf16 slot): "
              f"occupancy {smoke['mean_occupancy']:.3f}; 2x4 = "
              f"{sm['per_device_bytes_per_step']} B/device/step over "
              f"{sm['kv_shards']} shards + {sm['interconnect']['total']} "
              f"interconnect B/step")

    if args.baseline:
        with open(args.baseline) as f:
            base = json.load(f)
        for fmt in formats:
            if fmt not in base:
                print(f"# baseline has no {fmt} entry; skipping gate")
                continue
            b, cur = base[fmt], results[fmt]
            occ_b = b["chunked"]["mean_occupancy"]
            occ_c = cur["chunked"]["mean_occupancy"]
            if occ_c < occ_b - OCC_TOLERANCE:
                print(f"# REGRESSION {fmt}: chunked occupancy {occ_c:.3f} "
                      f"< baseline {occ_b:.3f} - {OCC_TOLERANCE}")
                ok = False

            def _ratio(e):
                c, g = e["chunked"]["itl_s_p95"], e["eager"]["itl_s_p95"]
                return c / g if c and g else None

            rb, rc = _ratio(b), _ratio(cur)
            if rb is not None and rc is not None \
                    and rc > max(rb, 1.0) * ITL_RATIO_FACTOR:
                print(f"# REGRESSION {fmt}: chunked/eager itl_p95 ratio "
                      f"{rc:.3f} > baseline {rb:.3f} x {ITL_RATIO_FACTOR}")
                ok = False

    print(f"# chunked >= eager occupancy, chunked itl_p95 <= eager, paged "
          f"prefix reuse + resident-KV win, bstc weights <= bf16/2 + "
          f"measured/modeled reconciliation, spec tokens identical"
          f"{', baseline gate' if args.baseline else ''}: {ok}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2)
        print(f"# baseline -> {args.out}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
