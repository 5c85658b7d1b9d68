"""Production serving launcher: slot-level continuous batching over the MCBP
engine (per-slot positions, int8 / bgpp KV caches, request scheduler).

    PYTHONPATH=src python -m repro.launch.serve --arch phi4-mini-3.8b \\
        [--smoke] --kv-format int8 --requests 8 --slots 4 --seed 0 \\
        [--admission chunked|eager] [--chunk-budget 16] \\
        [--kv-layout slot|paged] [--page-size 8] [--shared-prefix 16] \\
        [--bgpp-rounds 4] [--bgpp-keep-ratio 0.25] \\
        [--weight-format bf16|int8|bstc] \\
        [--spec-decode] [--draft-gamma 4] [--draft-planes 4] \\
        [--server] [--disconnect-every 3] [--disconnect-after 1] \\
        [--trace-out trace.json] [--mesh 2,4 | --data 1 --model 1]

Without ``--smoke`` the model runs at its published widths, with random
weights drawn from ``--seed`` (phi4-mini-3.8b needs one 16 GB accelerator);
``--smoke`` takes the config's reduced CPU variant.  Requests arrive on a
Poisson-ish trace with distinct prompt lengths and
decode budgets.  With the default ``--admission chunked`` the scheduler
feeds each arriving prompt through fixed-shape, bucketed prefill chunks
(jitted once per bucket, cache donated) interleaved with the batched decode
step, so a long prompt never stalls in-flight decoders for more than
``--chunk-budget`` prefill tokens; ``--admission eager`` keeps the
whole-prompt B=1 admission as the reference baseline.  ``--kv-layout
paged`` swaps the dense per-slot KV rows for pooled pages behind a page
table (host allocator with refcounts): requests sharing a system prompt
(``--shared-prefix``) reuse each other's resident prompt pages instead of
re-prefilling them, bit-identically to the slot layout.  ``--kv-format
bgpp`` decodes two-phase — bit-plane top-k prediction first
(``--bgpp-rounds``), then a full-precision gather of only the surviving
``--bgpp-keep-ratio`` fraction of keys — and the KV bytes each step read
are reported (``kv_read`` in the stats/trace).  ``--weight-format``
flips the decode projections onto the serve-time weight path
(``repro.serving.weights``): int8/bstc quantized records with the
``weight_read`` byte counter priced from the BSTC coded layout, bf16 the
bit-for-bit raw default.  ``--spec-decode`` turns on bit-plane
speculative decoding (``repro.serving.spec_decode``): a
``--draft-planes``-truncated copy of the serve weights drafts
``--draft-gamma`` tokens per slot per round, a batched verify chain
accepts/rolls back, and the printed stats gain an accepted-tokens/step
acceptance line — the generated tokens stay bit-identical to the
non-speculative run.  ``--trace-out`` dumps
per-request latency/queue-wait plus TTFT/ITL p50/p95 and aggregate
throughput as JSON so runs are reproducible (``--seed``) and comparable
across PRs.

``--server`` routes the same trace through the asyncio front door
(``repro.serving.server``) with simulated clients instead of the offline
replay loop: tiers rotate interactive/batch (interactive preempts batch
chunked prefills), and every ``--disconnect-every``-th client hangs up
after ``--disconnect-after`` streamed tokens — a mid-flight cancellation
that must evict the slot and free its pages.  The printed stats grow
cancellation / preemption / per-tier TTFT+ITL lines (the async-server CI
smoke greps them), and the per-step ``PageAllocator.check()`` leak gate
runs throughout.
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Callable, Optional

import numpy as np

import jax

from repro.configs import (ARCH_REGISTRY, WEIGHT_FORMATS,
                           apply_bgpp_overrides,
                           apply_decode_kernel_override,
                           apply_spec_decode_overrides,
                           apply_weight_format_override, get_config)
from repro.distributed import sharding as sh
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import model_zoo
from repro.serving import kv_cache as kvc
from repro.serving import sharded as shd
from repro.serving.request import poisson_trace
from repro.serving.scheduler import Scheduler


def device_label() -> str:
    """``platform device_kind xcount`` of the devices JAX runs on — every
    printed rate names the hardware it was measured on."""
    devs = jax.devices()
    return f"{devs[0].platform} {devs[0].device_kind} x{len(devs)}"


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=sorted(ARCH_REGISTRY),
                    default="phi4-mini-3.8b")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the config's reduced smoke variant (CPU "
                         "tests) instead of the published widths")
    ap.add_argument("--kv-format", default="int8",
                    choices=["bf16", "int8", "bgpp"])
    ap.add_argument("--kv-layout", default="slot", choices=["slot", "paged"],
                    help="paged: pooled KV pages + per-slot page table with "
                         "hash-based prefix reuse (bit-identical to slot)")
    ap.add_argument("--page-size", type=int, default=8,
                    help="tokens per KV page (paged layout)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many shared system-prompt tokens to "
                         "every request (exercises paged prefix reuse)")
    ap.add_argument("--bgpp-rounds", type=int, default=None,
                    help="progressive-prediction rounds for --kv-format "
                         "bgpp (default: the config's, usually 4)")
    ap.add_argument("--decode-kernel", default=None,
                    choices=["auto", "jnp", "interpret", "kernel"],
                    help="global-layer decode attend path: jnp (legacy), "
                         "interpret/kernel (Pallas paged-attention "
                         "families), auto = kernel on TPU (default: "
                         "config's; env REPRO_DECODE_KERNEL overrides)")
    ap.add_argument("--bgpp-keep-ratio", type=float, default=None,
                    help="fraction of keys fetched at full precision by "
                         "the bgpp top-k decode (default: the config's, "
                         "usually 0.25)")
    ap.add_argument("--weight-format", default=None,
                    choices=sorted(WEIGHT_FORMATS),
                    help="serve-time weight numerics for the decode "
                         "projections: bf16 (raw leaves, bit-for-bit "
                         "default), int8, or bstc (two-state coded pricing "
                         "in weight_read) (default: config's; env "
                         "REPRO_WEIGHT_FORMAT overrides)")
    ap.add_argument("--spec-decode", action="store_true",
                    help="bit-plane speculative decoding: truncated-plane "
                         "draft weights propose --draft-gamma tokens per "
                         "slot per round, verified and rolled back in one "
                         "batched chain (bit-identical output; env "
                         "REPRO_SPEC_DECODE overrides)")
    ap.add_argument("--draft-gamma", type=int, default=None,
                    help="draft tokens per slot per speculative round "
                         "(default: the config's, usually 4)")
    ap.add_argument("--draft-planes", type=int, default=None,
                    help="MSB magnitude bit-planes kept in the draft "
                         "weights, 1-8; >= 7 keeps every bit (default: the "
                         "config's, usually 4)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--admission", default="chunked",
                    choices=["chunked", "eager"],
                    help="chunked: bucketed jitted prefill interleaved with "
                         "decode; eager: whole-prompt B=1 admission")
    ap.add_argument("--chunk-budget", type=int, default=16,
                    help="max prefill tokens between consecutive batched "
                         "decode steps (chunked admission)")
    ap.add_argument("--arrival-rate", type=float, default=2.0,
                    help="mean inter-arrival gap in decode steps")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the request trace "
                         "(reproducible runs)")
    ap.add_argument("--server", action="store_true",
                    help="drive the asyncio front door with simulated "
                         "tiered streaming clients (interactive/batch "
                         "rotation, mid-stream disconnects) instead of the "
                         "offline replay loop")
    ap.add_argument("--disconnect-every", type=int, default=3,
                    help="--server: every Nth client disconnects mid-stream "
                         "(0 disables)")
    ap.add_argument("--disconnect-after", type=int, default=1,
                    help="--server: disconnecting clients hang up after "
                         "this many streamed tokens")
    ap.add_argument("--trace-out", default=None,
                    help="write per-request latency/throughput JSON here")
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--mesh", default=None,
                    help="DATA,MODEL device-mesh shape (e.g. 2,4); overrides "
                         "--data/--model.  Needs data*model visible devices "
                         "(XLA_FLAGS=--xla_force_host_platform_device_count=8 "
                         "on CPU)")
    return ap


def init_params(cfg, seed: int, rules: sh.ShardingRules = sh.ShardingRules()):
    """Random weights for ``cfg`` from ``seed``, built by one jitted program
    so each leaf is written once, in its own dtype, straight into device
    memory (no eager per-op temporaries at published widths) — on a mesh,
    directly in the serving placement, so no device ever holds the whole
    model."""
    key = jax.random.key(seed)
    shardings = None
    if rules.mesh is not None:
        shapes = jax.eval_shape(lambda k: model_zoo.init_params(k, cfg), key)
        shardings = shd.param_shardings(
            shapes, model_zoo.param_specs(cfg), rules
        )
    init = jax.jit(model_zoo.init_params, static_argnums=1,
                   out_shardings=shardings)
    return init(key, cfg)


def run(args, params=None, *, record_logits: bool = False,
        on_step: Optional[Callable[[Scheduler], None]] = None):
    """Serve one request trace as ``args`` describe -> ``(stats, sched)``.

    ``params`` (else random weights from ``args.seed``) lets one process
    serve several cache formats with the same weights; ``record_logits``
    keeps every emitted token's logits row on its request; ``on_step`` is
    called with the scheduler after every offline-loop step.
    """
    if args.mesh:
        args.data, args.model = shd.parse_mesh_arg(args.mesh)

    cfg = apply_bgpp_overrides(
        get_config(args.arch, smoke=args.smoke),
        rounds=args.bgpp_rounds, keep_ratio=args.bgpp_keep_ratio,
    )
    cfg = apply_decode_kernel_override(cfg, args.decode_kernel)
    cfg = apply_weight_format_override(cfg, args.weight_format)
    cfg = apply_spec_decode_overrides(cfg, enabled=args.spec_decode or None,
                                      gamma=args.draft_gamma,
                                      planes=args.draft_planes)
    if cfg.family not in ("dense", "moe", "vlm"):
        raise SystemExit("continuous batching driver covers transformer "
                         "families; ssm/hybrid/enc-dec decode in tests/")
    mesh = make_mesh(args.data, args.model)
    rules = sh.rules_for_mesh(mesh)
    rng = np.random.default_rng(args.seed)
    if params is None:
        params = init_params(cfg, args.seed, rules)

    layout = kvc.layout_for(cfg, args.slots, args.max_seq,
                            kv_format=args.kv_format,
                            layout=args.kv_layout, page_size=args.page_size)
    sched = Scheduler(params, cfg, layout, rules,
                      admission=args.admission,
                      chunk_budget=args.chunk_budget,
                      prefill_kw=dict(block_q=16, block_k=32),
                      record_logits=record_logits)
    max_prompt = min(23, args.max_seq - 2 - args.shared_prefix)
    assert max_prompt >= 1, "--shared-prefix leaves no room for prompts"
    reqs = poisson_trace(rng, args.requests, cfg.vocab_size,
                         args.max_new, args.arrival_rate,
                         max_prompt=max_prompt,
                         shared_prefix=args.shared_prefix)

    t0 = time.perf_counter()
    if args.server:
        from repro.serving.server import simulate_clients
        with mesh:
            stats = simulate_clients(
                sched, reqs, disconnect_every=args.disconnect_every,
                disconnect_after=args.disconnect_after,
            )
        dt = time.perf_counter() - t0
        stats["wall_s"] = round(dt, 3)
        stats["tokens_per_s"] = round(stats["decoded_tokens"] / dt, 2) \
            if dt > 0 else None
    else:
        for req in reqs:
            sched.submit(req)
        done = 0
        with mesh:
            while sched.num_pending:
                sched.step()
                if on_step is not None:
                    on_step(sched)
                if len(sched.finished) != done:
                    done = len(sched.finished)
                    print(f"[serve] {done}/{args.requests} requests "
                          f"({sched.decoded_tokens} tokens, "
                          f"step {sched.step_count})")
        dt = time.perf_counter() - t0
        stats = sched.stats(dt)
    print(f"[serve] arch={cfg.name} kv={args.kv_format} "
          f"admission={args.admission}: "
          f"{stats['finished_requests']} requests, "
          f"{stats['decoded_tokens']} tokens in {dt:.1f}s "
          f"({stats['tokens_per_s']:.1f} tok/s on {device_label()}, "
          f"mean occupancy {stats['mean_occupancy']:.2f})")
    print(f"[serve] ttft_s p50={stats['ttft_s']['p50']} "
          f"p95={stats['ttft_s']['p95']}  "
          f"itl_s p50={stats['itl_s']['p50']} p95={stats['itl_s']['p95']}  "
          f"max prefill tokens/step={stats['max_prefill_tokens_per_step']}")
    if args.server:
        pages = (f" pages_in_use={stats['paged']['pages_in_use']}"
                 if "paged" in stats else "")
        print(f"[serve] server: cancelled={stats['cancelled_requests']} "
              f"shed={stats['shed_requests']} "
              f"preemptions={stats['preemptions']} "
              f"disconnects="
              f"{sum(c['disconnected'] for c in stats['clients'])}{pages}")
        for tier, t in stats["tiers"].items():
            print(f"[serve] tier {tier}: finished={t['finished']} "
                  f"cancelled={t['cancelled']} shed={t['shed']} "
                  f"preemptions={t['preemptions']} "
                  f"ttft_s p50={t['ttft_s']['p50']} "
                  f"itl_s p50={t['itl_s']['p50']} p95={t['itl_s']['p95']}")
    kv = stats["kv_read"]
    print(f"[serve] kv read: {kv['decode_bytes']/1e6:.2f} MB decode + "
          f"{kv['prefill_bytes']/1e6:.2f} MB prefill; "
          f"{kv['decode_bytes_per_step']/1e3:.1f} kB/decode-step "
          f"(bf16-equivalent {kv['decode_bf16_equiv_bytes_per_step']/1e3:.1f}"
          f" kB, {kv['decode_bytes_reduction_vs_bf16']}x reduction)")
    print(f"[serve] mesh {kv['mesh']['data']}x{kv['mesh']['model']} "
          f"({kv['kv_shards']} kv shards): "
          f"{kv['decode_bytes_per_device_per_step']/1e3:.1f} kB/device/step, "
          f"interconnect {kv['interconnect_bytes_per_step']/1e3:.2f} kB/step "
          f"({kv['interconnect_bytes']/1e6:.2f} MB total: all-reduces "
          f"{kv['interconnect']['attn_out_allreduce']/1e3:.2f} kB/step after "
          f"wo + {kv['interconnect']['mlp_out_allreduce']/1e3:.2f} after the "
          f"MLP + paged write bcast "
          f"{kv['interconnect']['paged_write_bcast']/1e3:.2f})")
    wr = stats["weight_read"]
    print(f"[serve] weight read ({wr['weight_format']}): "
          f"{wr['decode_bytes']/1e6:.2f} MB decode + "
          f"{wr['prefill_bytes']/1e6:.2f} MB prefill; "
          f"{wr['decode_bytes_per_step']/1e3:.1f} kB/decode-step "
          f"(bf16-equivalent "
          f"{wr['decode_bf16_equiv_bytes_per_step']/1e3:.1f} kB, "
          f"{wr['decode_bytes_reduction_vs_bf16']}x reduction, "
          f"measured/modeled {wr['measured_over_modeled']})")
    if "spec" in stats:
        sp = stats["spec"]
        print(f"[serve] spec decode (gamma={sp['gamma']}, "
              f"planes={sp['draft_planes']}, source={sp['draft_source']}): "
              f"accepted/step={sp['accepted_tokens_per_step']:.3f} "
              f"({sp['accepted_tokens']} tokens, {sp['rounds']} rounds, "
              f"{sp['accepted_tokens_per_round']:.2f}/round, draft hit rate "
              f"{sp['draft_hit_rate']:.2f})")
        print(f"[serve] spec bytes/accepted-token: "
              f"kv {sp['kv_bytes_per_accepted_token']/1e3:.1f} kB, "
              f"weight {sp['weight_bytes_per_accepted_token']/1e3:.1f} kB "
              f"(modeled bit-plane draft "
              f"{sp['modeled_weight_bytes_per_accepted_token']/1e3:.1f} kB)")
    if "bgpp" in kv:
        bg = kv["bgpp"]
        print(f"[serve] bgpp two-phase: {bg['rounds']} rounds, "
              f"{bg['full_rows_per_slot']} full-precision rows per "
              f"(slot, layer) per step; per-step bytes = "
              f"sign {bg['sign_bytes']/1e3:.1f} kB + planes "
              f"{bg['plane_bytes']/1e3:.1f} kB + top-k full "
              f"{bg['topk_full_bytes']/1e3:.1f} kB")
    if "paged" in stats:
        pg = stats["paged"]
        print(f"[serve] paged: prefix hit rate {pg['prefix_hit_rate']:.3f} "
              f"({pg['prefix_hit_tokens']} tokens over {pg['prefix_hits']} "
              f"hits), resident KV peak {pg['resident_kv_bytes_peak']/1e3:.1f}"
              f" kB vs {pg['slot_resident_kv_bytes']/1e3:.1f} kB slot-dense, "
              f"pages_in_use={pg['pages_in_use']}")
    if args.trace_out:
        stats["config"] = {
            "arch": cfg.name, "kv_format": args.kv_format,
            "kv_layout": args.kv_layout, "page_size": args.page_size,
            "shared_prefix": args.shared_prefix,
            "slots": args.slots, "max_seq": args.max_seq,
            "requests": args.requests, "max_new": args.max_new,
            "admission": args.admission, "chunk_budget": args.chunk_budget,
            "arrival_rate": args.arrival_rate, "seed": args.seed,
            "smoke": args.smoke, "device": device_label(),
            "mesh": [args.data, args.model],
            "bgpp_rounds": cfg.mcbp.bgpp_rounds,
            "bgpp_keep_ratio": cfg.mcbp.bgpp_keep_ratio,
            "decode_kernel": cfg.mcbp.decode_kernel,
            "weight_format": sched.weight_format,
            "spec_decode": sched.spec.enabled,
            "draft_gamma": sched.spec.gamma,
            "draft_planes": sched.spec.planes,
            "server": args.server,
            "disconnect_every": args.disconnect_every,
            "disconnect_after": args.disconnect_after,
        }
        with open(args.trace_out, "w") as f:
            json.dump(stats, f, indent=2)
        print(f"[serve] trace -> {args.trace_out}")
    return stats, sched


def main():
    enable_compile_cache()
    run(build_parser().parse_args())


if __name__ == "__main__":
    main()
