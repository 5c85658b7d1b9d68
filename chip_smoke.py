"""Serve a published-width model on the TPU through the normal launcher.

    python chip_smoke.py [--seed 0]        # phi4-mini-3.8b on one chip
    python chip_smoke.py --four-chips      # deepseek-7b on a 1x4 mesh

One process, one model at its published widths, random weights from
``--seed``, requests served through ``repro.launch.serve.run`` exactly as
the launcher serves them.  With no option, phi4-mini-3.8b (32 layers,
d_model 3072, GQA 24/8, vocab 200,064) is served on 8 slots with
max_seq 1024 in three KV formats, one scheduler each, sharing the weights:

* ``int8`` KV, slot layout (the launcher default);
* ``bgpp`` KV, paged layout, with a shared prompt prefix that the prefix
  index reuses (both decode kernel families run on this path);
* ``bf16`` KV, slot layout.

Checks, each of which ends the run with a non-zero exit code:

* the backend is a TPU, the decode attend resolves to the compiled
  kernels and kernel dispatch to ``compiled`` — nothing falls back to the
  CPU, to interpret mode or to the jnp attend;
* every request finishes with its full token budget;
* the served decode step attends through a Mosaic kernel;
* int8 and bgpp: one decode step through the compiled kernels against the
  same step through the engine's jnp attend, on a copy of the live cache
  taken at the step with the most slots decoding (the first two layers,
  so that both programs compile in seconds):
  ``max|d logits| <= KERNEL_TOL * max|logits|``;
* bf16: every emitted token's logits against ``model_zoo.forward`` (plain
  XLA, no Pallas) over prompt plus generated tokens, teacher-forced:
  ``rms(d logits) <= BF16_TOL * rms(logits)`` per request.

``--four-chips`` runs only deepseek-7b (30 layers, d_model 4096, MHA 32
heads, 13.8 GB in bf16) on a ``1x4`` (data, model) mesh with int8 KV, the
kernel decode running under ``shard_map``, compared with
``model_zoo.forward`` on the same mesh (``INT8_TOL``), and checks that
every device holds the weight bytes the serving placement assigns it: a
quarter of every matrix (tensor parallel over ``"model"``), and the
replicated norms whole.

Compile seconds, requests and tokens finished and peak device memory are
printed per phase.  The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  The
compilation cache goes where ``repro.launch.compile_cache`` puts it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

# decode-step logits, compiled kernels vs the engine's jnp attend: both
# paths share every integer step (int8 dots, BGPP plane scores and the
# selected sets); the f32 softmax may round in another order, which can
# move one int8 quantization step of the weighted probs (<= 1/127 of the
# row's largest weight) and is then carried through bf16 matmuls (2**-8
# relative) -> 2% of the logits' magnitude
KERNEL_TOL = 2e-2
# bf16 KV serving vs the XLA forward: two bf16 programs that round
# differently (chunked prefill and the decode kernel vs blocked
# attention), 2**-8 relative per rounding through 32 layers.  5% RMS
# allows that drift; a wrong page, position or head gives errors of the
# order of the logits themselves
BF16_TOL = 5e-2
# int8 KV serving vs the bf16 XLA forward: q, K, V and the weighted probs
# are quantized to 8 bits (0.4-0.8% steps) in every one of 30 layers
INT8_TOL = 1.5e-1


def fail(msg: str) -> None:
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileLog:
    """Backend compile seconds per jitted function, from JAX's monitoring
    events; :meth:`report` prints and clears what one phase compiled."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.rows = []
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **kw):
        if event == self.EVENT:
            self.rows.append((kw.get("fun_name", "?"), duration))

    def report(self, phase: str) -> None:
        total = sum(d for _, d in self.rows)
        big = ", ".join(f"{n} {d:.1f}s" for n, d in self.rows if d >= 1.0)
        log(f"{phase}: {len(self.rows)} compiles, {total:.1f} s"
            + (f" ({big})" if big else ""))
        self.rows = []


def peak_bytes(jax) -> str:
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return ", ".join(
        f"{s.get('peak_bytes_in_use', 0) / 2**30:.2f}" for s in stats
    ) + " GiB peak_bytes_in_use"


def serve_args(serve, extra, seed):
    return serve.build_parser().parse_args(
        ["--slots", "8", "--max-seq", "1024", "--requests", "8",
         "--max-new", "48", "--chunk-budget", "4", "--arrival-rate", "0.5",
         "--seed", str(seed)] + extra
    )


def check_kernels(jax, sched, phase):
    """The served decode step attends through a Mosaic kernel.  The route
    is chosen once per step build for every global layer, so one kernel in
    the lowered program means all of them; lowering is cheap, compiling
    again is not (and under ``shard_map`` the lowered program holds the
    kernel once, in the outlined per-device body)."""
    tokens = jax.numpy.asarray(sched.tokens)
    text = sched.serve_step.lower(
        sched.serve_params, sched.cache, tokens).as_text()
    n = text.count("tpu_custom_call")
    log(f"{phase}: lowered decode step holds {n} tpu_custom_call sites "
        f"({sched.cfg.num_layers} layers)")
    if n < 1:
        fail(f"{phase}: the served step does not run the decode kernels")


def check_finished(stats, sched, args, phase):
    got = stats["finished_requests"]
    if got != args.requests:
        fail(f"{phase}: {got} of {args.requests} requests finished")
    short = [r.rid for r in sched.finished
             if len(r.generated) != r.max_new_tokens]
    if short:
        fail(f"{phase}: requests {short} ended short of their budget")
    log(f"{phase}: {got} requests, "
        f"{sum(len(r.generated) for r in sched.finished)} tokens "
        f"({stats['decoded_tokens']} from decode steps) finished")


class BusiestStepSnapshot:
    """``on_step`` hook: a copy of the cache and next tokens at the first
    step with the most slots decoding (prefill donates the live cache, so
    the snapshot copies it)."""

    def __init__(self, jax):
        self.jax = jax
        self.best = -1
        self.cache = self.tokens = self.live = None

    def __call__(self, sched):
        live = [s.index for s in sched.slots if s.state.name == "DECODING"]
        if len(live) > self.best:
            self.best = len(live)
            self.cache = self.jax.tree.map(self.jax.numpy.copy, sched.cache)
            self.tokens = sched.tokens.copy()
            self.live = live


def kernel_vs_jnp(jax, sched, snap, phase, n_layers=2):
    """One decode step through the compiled kernels and through the jnp
    attend, on the snapshot's live cache, over the first layers."""
    import numpy as np

    from repro.serving import engine, kv_cache as kvc

    cfg = dataclasses.replace(sched.cfg, num_layers=n_layers)
    lay = sched.layout
    layout = kvc.layout_for(cfg, lay.batch, lay.max_seq,
                            kv_format=lay.kv_format, layout=lay.layout,
                            page_size=lay.page_size)
    cut = lambda tree: jax.tree.map(lambda a: a[:n_layers], tree)
    params = dict(sched.serve_params, layers=cut(sched.serve_params["layers"]))
    cache = dict(snap.cache, **{"global": cut(snap.cache["global"])})
    tokens = jax.numpy.asarray(snap.tokens)
    out = {}
    for mode in ("kernel", "jnp"):
        c = dataclasses.replace(
            cfg, mcbp=dataclasses.replace(cfg.mcbp, decode_kernel=mode))
        step = jax.jit(engine.make_serve_step(c, layout, sched.rules))
        step = step.lower(params, cache, tokens).compile()
        if ("tpu_custom_call" in step.as_text()) != (mode == "kernel"):
            fail(f"{phase}: the {mode} step has the wrong attend")
        logits, _ = step(params, cache, tokens)
        out[mode] = np.asarray(logits[snap.live, -1], np.float32)
    ref = out["jnp"]
    err = float(np.max(np.abs(out["kernel"] - ref)))
    scale = float(np.max(np.abs(ref)))
    log(f"{phase}: kernel vs jnp decode step over {len(snap.live)} live "
        f"slots: max|d| {err:.3e} = {err / scale:.3e} of max|logits| "
        f"{scale:.3e} (tolerance {KERNEL_TOL})")
    if not np.isfinite(out["kernel"]).all() or err > KERNEL_TOL * scale:
        fail(f"{phase}: compiled kernel step disagrees with the jnp step")


def teacher_forced(jax, sched, params, tol, phase):
    """Emitted-token logits vs ``model_zoo.forward`` over prompt plus
    generated tokens (right-padded into one batch: causal attention keeps
    every compared position independent of the padding)."""
    import numpy as np

    from repro.models import model_zoo

    reqs = sorted(sched.finished, key=lambda r: r.rid)
    seqs = [list(r.prompt) + list(r.generated[:-1]) for r in reqs]
    L = max(len(s) for s in seqs)
    L += -L % 64
    toks = np.zeros((len(seqs), L), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    fwd = jax.jit(lambda p, t: model_zoo.forward(
        p, sched.cfg, {"tokens": t}, sched.rules, block_q=64, block_k=64)[0])
    with sched.rules.mesh:
        ref_all = np.asarray(fwd(params, jax.numpy.asarray(toks)), np.float32)
    worst = 0.0
    for i, r in enumerate(reqs):
        got = np.stack(r.logit_rows)  # (G, V): row t predicted generated[t]
        P = len(r.prompt)
        ref = ref_all[i, P - 1:P - 1 + len(got)]
        if not np.isfinite(got).all():
            fail(f"{phase}: request {r.rid} has non-finite logits")
        rel = float(np.sqrt(np.mean((got - ref) ** 2) / np.mean(ref ** 2)))
        agree = float(np.mean(np.argmax(got, -1) == np.argmax(ref, -1)))
        worst = max(worst, rel)
        log(f"{phase}: request {r.rid} ({P} prompt + {len(got)} generated): "
            f"rms(d)/rms(ref) {rel:.3e}, argmax agreement {agree:.2f}")
    log(f"{phase}: teacher-forced vs model_zoo.forward: worst "
        f"{worst:.3e} (tolerance {tol})")
    if worst > tol:
        fail(f"{phase}: served logits drift from the reference forward")


def require_compiled(cfg) -> None:
    """The decode attend must resolve to the compiled Mosaic kernels."""
    from repro.kernels import dispatch
    from repro.serving import kernel_decode

    mode, disp = kernel_decode.resolve(cfg), dispatch.resolve_mode()
    if mode != "compiled" or disp != "compiled":
        fail(f"decode attend resolves to {mode!r} and kernel dispatch to "
             f"{disp!r}, not the compiled kernels")


def one_chip(jax, serve, seed, clog):
    from repro.configs import get_config
    from repro.distributed import sharding as sh
    from repro.launch.mesh import make_mesh

    cfg = get_config("phi4-mini-3.8b")
    require_compiled(cfg)
    # placed as run() places them (one-device mesh): no copy at admission
    rules = sh.rules_for_mesh(make_mesh(1, 1))
    t0 = time.perf_counter()
    params = serve.init_params(cfg, seed, rules)
    jax.block_until_ready(params)
    n = sum(x.size for x in jax.tree.leaves(params))
    log(f"phi4-mini-3.8b: {n / 1e9:.3f} B parameters initialised in "
        f"{time.perf_counter() - t0:.1f} s; {peak_bytes(jax)}")
    clog.report("init")

    phases = [
        ("int8", ["--kv-format", "int8"]),
        ("bgpp", ["--kv-format", "bgpp", "--kv-layout", "paged",
                  "--page-size", "32", "--shared-prefix", "64",
                  "--bgpp-keep-ratio", "0.0625"]),
        ("bf16", ["--kv-format", "bf16"]),
    ]
    for name, extra in phases:
        args = serve_args(serve, extra, seed)
        snap = BusiestStepSnapshot(jax)
        t0 = time.perf_counter()
        stats, sched = serve.run(args, params, record_logits=name == "bf16",
                                 on_step=snap)
        log(f"{name}: served in {time.perf_counter() - t0:.1f} s "
            f"(compile included); {peak_bytes(jax)}")
        clog.report(f"{name} serving")
        check_finished(stats, sched, args, name)
        check_kernels(jax, sched, name)
        if name == "bgpp":
            hits = stats["paged"]["prefix_hit_tokens"]
            log(f"bgpp: prefix index reused {hits} prompt tokens")
            if hits <= 0:
                fail("bgpp: the shared prefix was never reused")
        if name == "bf16":
            teacher_forced(jax, sched, params, BF16_TOL, name)
        else:
            kernel_vs_jnp(jax, sched, snap, name)
        clog.report(f"{name} checks")
        del sched, snap


def four_chips(jax, serve, seed, clog):
    import numpy as np

    if len(jax.devices()) < 4:
        fail(f"--four-chips needs 4 devices, JAX found {len(jax.devices())}")
    from repro.configs import get_config
    from repro.distributed import sharding as sh
    from repro.launch.mesh import make_mesh

    cfg = get_config("deepseek-7b")
    require_compiled(cfg)
    rules = sh.rules_for_mesh(make_mesh(1, 4))
    t0 = time.perf_counter()
    params = serve.init_params(cfg, seed, rules)
    jax.block_until_ready(params)
    log(f"deepseek-7b: initialised on a 1x4 mesh in "
        f"{time.perf_counter() - t0:.1f} s; {peak_bytes(jax)}")
    clog.report("init")

    # per-device weight bytes: what the serving placement assigns vs held
    leaves = jax.tree.leaves(params)
    total = sum(x.nbytes for x in leaves)
    held = {d.id: 0 for d in jax.devices()[:4]}
    for x in leaves:
        for s in x.addressable_shards:
            held[s.device.id] += s.data.nbytes
    expect = sum(
        int(np.prod(x.sharding.shard_shape(x.shape))) * x.dtype.itemsize
        for x in leaves)
    log(f"weights: {total / 2**30:.2f} GiB in all; per device "
        + ", ".join(f"{b / 2**30:.2f}" for b in held.values())
        + f" GiB; the serving placement assigns {expect / 2**30:.2f} GiB "
        f"({expect / total:.3f} of the total) to each")
    whole = sum(x.nbytes for x in leaves if x.sharding.is_fully_replicated)
    if any(b != expect for b in held.values()) or \
            expect != (total - whole) // 4 + whole:
        fail("weights are not split across the four devices as the "
             "serving placement says: a quarter of every matrix, the "
             "norms whole")

    args = serve_args(serve, ["--arch", "deepseek-7b", "--kv-format", "int8",
                              "--mesh", "1,4"], seed)
    t0 = time.perf_counter()
    stats, sched = serve.run(args, params, record_logits=True)
    log(f"deepseek-7b int8 1x4: served in {time.perf_counter() - t0:.1f} s "
        f"(compile included); {peak_bytes(jax)}")
    clog.report("serving")
    check_finished(stats, sched, args, "deepseek-7b")
    check_kernels(jax, sched, "deepseek-7b")
    teacher_forced(jax, sched, params, INT8_TOL, "deepseek-7b")
    clog.report("checks")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and request traces")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the deepseek-7b 1x4 mesh phase")
    opts = ap.parse_args()

    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        fail(f"no repro package under {src}: run from a checkout")
    sys.path.insert(0, str(src))
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU; JAX found platform {dev.platform!r} "
             f"({dev.device_kind})")
    from repro.launch import serve
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    clog = CompileLog(jax)
    log(f"device: {serve.device_label()}")
    if opts.four_chips:
        four_chips(jax, serve, opts.seed, clog)
    else:
        one_chip(jax, serve, opts.seed, clog)
    devs = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
