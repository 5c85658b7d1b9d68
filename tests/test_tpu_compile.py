"""The served decode kernels and step programs compile for a TPU v5e — no
chip needed.

Each kernel test lowers one Pallas kernel at phi4-mini-3.8b's published
attention widths (8 kv heads, GQA group 3, head_dim 128) and the pool sizes
``chip_smoke.py`` serves (8 slots, max_seq 1024) — the paged flash kernel
also at the benchmark cells' pools — and compiles it for a described
``v5e:2x2`` with the TPU compiler installed here.  That is what
interpret mode cannot show: Mosaic refuses block shapes off the (8, 128)
tiling and kernels that overrun the scoped VMEM.  The step tests compile the
int8 slot-layout decode step and a prefill chunk at the same widths (a few
layers) and read what the compiler made of the cache: no relayout of a whole
K/V stack, no copy of a K/V layer or of a scale stack in the decode step,
and a decode step that updates its donated cache in place.
Nothing runs.
"""

import dataclasses
import math
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.bgpp_paged_attend.kernel import bgpp_paged_attend_pallas
from repro.kernels.paged_flash_decode.kernel import (
    paged_flash_decode_pallas, slot_page_rows, slot_table,
)
from repro.models import model_zoo
from repro.serving import engine, kv_cache as kvc

SLOTS, MAX_SEQ = 8, 1024  # chip_smoke.py's serving shape


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Shape factory on one described v5e chip, with the persistent
    compilation cache off (a compile for a described chip is written to it
    but cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    sharding = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding
    )
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _cfg():
    return get_config("phi4-mini-3.8b")


def _pool(layout_kind, fmt, page_size):
    """(n_tok, page_size, pages_per_slot) of the pool the bgpp kernel sees:
    the paged layout's own pool, or the slot stack viewed as one page per
    slot."""
    if layout_kind == "slot":
        return SLOTS * MAX_SEQ, MAX_SEQ, 1
    lay = kvc.layout_for(_cfg(), SLOTS, MAX_SEQ, kv_format=fmt,
                         layout="paged", page_size=page_size)
    return lay.num_pages * page_size, page_size, lay.pages_per_slot


def _compile(f, *args):
    compiled = jax.jit(f).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# (config, slots, max_seq, model-axis shards): chip_smoke.py's phi4 pool,
# the phi4-chat-int8 cell's, and one device's shard of the
# deepseek7b-longdoc-1x4 cell's (8 of 32 kv heads)
FLASH_SHAPES = [("phi4-mini-3.8b", SLOTS, MAX_SEQ, 1),
                ("phi4-mini-3.8b", 20, 2048, 1),
                ("deepseek-7b", 32, 4096, 4)]


@pytest.mark.parametrize("fmt", ["bf16", "int8"])
@pytest.mark.parametrize("layout_kind,page_size",
                         [("slot", None), ("paged", 8)])
@pytest.mark.parametrize("name,slots,max_seq,shards", FLASH_SHAPES)
def test_paged_flash_decode_compiles(one_chip, fmt, layout_kind, page_size,
                                     name, slots, max_seq, shards):
    """The kernel takes a two-layer stack and attends its second layer;
    a slot stack is merged to ``(L, B*S, Hk*D)`` and read in sub-pages,
    with its int8 scales read in place as ``(L, B, Hk, S)`` rows.  No op
    outside the kernel slices, copies or relays one layer of K/V (at small
    pools XLA may still prefetch a whole pool into VMEM: those moves are
    ``slice-start``/``-done`` pieces, not a relayout)."""
    cfg = get_config(name)
    Hk, D = cfg.num_kv_heads // shards, cfg.head_dim
    g = cfg.num_heads // cfg.num_kv_heads
    dt = jnp.bfloat16 if fmt == "bf16" else jnp.int8
    L = 2
    if layout_kind == "slot":
        P = slot_page_rows(max_seq, Hk * D, jnp.dtype(dt).itemsize)
        kv = (L, slots, max_seq, Hk * D)
        scale = (L, slots, Hk, max_seq)
        table = lambda: slot_table(slots, max_seq, P)
    else:
        lay = kvc.layout_for(cfg, slots, max_seq, kv_format=fmt,
                             layout="paged", page_size=page_size)
        P, n_tok = page_size, lay.num_pages * page_size
        kv = (L, n_tok, Hk * D)
        scale = (L, n_tok, Hk)
        table = lambda: jnp.zeros((slots, lay.pages_per_slot), jnp.int32)
    args = [one_chip((slots, Hk, g, D), jnp.float32),
            one_chip(kv, dt), one_chip(kv, dt), one_chip((slots,), jnp.int32)]
    if fmt == "int8":
        args += [one_chip(scale, jnp.float32)] * 2

    def f(q, k, v, pos, *scales):
        ks, vs = scales or (None, None)
        k = k.reshape(L, -1, Hk * D)
        v = v.reshape(L, -1, Hk * D)
        return paged_flash_decode_pallas(q, k, v, table(), pos, 1,
                                         page_size=P, k_scale=ks, v_scale=vs)

    compiled = _compile(f, *args)
    layer = math.prod(kv) // L
    made = [c for c in _entry_ops(compiled, r"(?:copy|slice|fusion|transpose)")
            if c[1] >= layer]
    assert not made, f"the kernel's operands are copied: {made}"


@pytest.mark.parametrize("layout_kind,page_size",
                         [("paged", 32), ("slot", None)])
def test_bgpp_paged_attend_compiles(one_chip, layout_kind, page_size):
    """Paged with 32-token pages is chip_smoke.py's bgpp run; the kernel
    stages one slot's pages at a time (2 MiB of pool rows at these
    widths), whatever the pool size."""
    cfg = _cfg()
    Hk, D = cfg.num_kv_heads, cfg.head_dim
    g = cfg.num_heads // Hk
    n_tok, P, pp = _pool(layout_kind, "bgpp", page_size)
    rounds, k_max, survivors = kvc.bgpp_decode_plan(MAX_SEQ, cfg)
    args = [one_chip((SLOTS, Hk, g, D), jnp.float32),
            one_chip((kvc.NBITS, n_tok, Hk, D // 8), jnp.uint8),
            one_chip((n_tok, Hk, D // 8), jnp.uint8),
            one_chip((n_tok, Hk), jnp.float32),
            one_chip((n_tok, Hk, D), jnp.int8),
            one_chip((n_tok, Hk), jnp.float32),
            one_chip((SLOTS, pp), jnp.int32), one_chip((SLOTS,), jnp.int32)]
    _compile(
        lambda *a: bgpp_paged_attend_pallas(
            *a, page_size=P, rounds=rounds, k_max=k_max, survivors=survivors
        ),
        *args,
    )


# --------------------------------------------------------------------------
# whole step programs: the int8 slot cache stays in place
# --------------------------------------------------------------------------

STEP_LAYERS = 2  # every stack is still (layers, slots, ...); compiles fast
CHUNK = 256  # the benchmark cell's chunk budget, its largest bucket


@pytest.fixture(scope="module")
def step_programs(one_chip):
    """The decode step as the scheduler jits it and one prefill-chunk
    bucket as ``ChunkedPrefill`` jits it, compiled for one described v5e
    with the compiled decode kernel, plus the cache's shapes."""
    cfg = dataclasses.replace(_cfg(), num_layers=STEP_LAYERS)
    lay = kvc.layout_for(cfg, SLOTS, MAX_SEQ, kv_format="int8")
    place = lambda tree: jax.tree.map(
        lambda s: one_chip(s.shape, s.dtype), tree)
    params = place(jax.eval_shape(
        lambda k: model_zoo.init_params(k, cfg), jax.random.key(0)))
    cache = place(jax.eval_shape(lambda: kvc.init_cache_arrays(cfg, lay)))
    i32 = lambda shape: one_chip(shape, jnp.int32)
    with pytest.MonkeyPatch.context() as mp:
        # the program picks its decode attend and kernel dispatch from the
        # default backend: compile the chip's program, not the CPU's
        mp.setattr(jax, "default_backend", lambda: "tpu")
        step = engine.jit_serve_step(cfg, lay).lower(
            params, cache, i32((SLOTS, 1))).compile()
        chunk = engine.ChunkedPrefill(cfg, lay, buckets=(CHUNK,))._chunk
        chunk = chunk.lower(params, cache, i32((1, CHUNK)), i32(()), i32(()),
                            i32(())).compile()
    assert "tpu_custom_call" in step.as_text()
    return {"serve_step": step, "prefill_chunk": chunk}, cache


# every opcode that makes an array, not a view of one
_MAKES = r"(?!bitcast\(|parameter\()[\w-]+"


def _entry_shapes(compiled, opcode=r"[\w-]+"):
    """``(dtype, dims)`` of every array the entry computation's ``opcode``
    ops make (``copy`` is a relayout; async memory-space moves are
    ``copy-start``/``-done``)."""
    text = compiled.as_text()
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in re.findall(
                rf"= (\w+)\[([\d,]*)\]\{{[^}}]*\}} {opcode}\(", entry)]


def _entry_ops(compiled, opcode=r"[\w-]+"):
    """``(dtype, element count)`` of :func:`_entry_shapes`."""
    return [(dt, math.prod(dims)) for dt, dims in _entry_shapes(compiled, opcode)]


@pytest.mark.parametrize("program", ["serve_step", "prefill_chunk"])
def test_step_program_relays_no_kv_stack(step_programs, program):
    """A heads-major stack written one token row at a time is relaid whole
    on the way in and out of each step program (four 1.34 GB copies a step
    at the benchmark's size); token-major stacks take the row in place."""
    progs, cache = step_programs
    stack = {a.size for n, a in cache["global"].items() if n in ("k", "v")}
    whole = [c for c in _entry_ops(progs[program], "copy")
             if c[0] == "s8" and c[1] in stack]
    assert not whole, f"{program} copies whole K/V stacks: {whole}"


def test_prefill_chunk_reads_one_slot(step_programs):
    """The chunk attends one slot's rows: no op makes a whole layer of the
    K/V stack (every slot's rows) on the way to that slot's 2 MB."""
    progs, cache = step_programs
    layer = {a.size // STEP_LAYERS
             for n, a in cache["global"].items() if n in ("k", "v")}
    whole = [c for c in _entry_ops(progs["prefill_chunk"])
             if c[0] == "s8" and c[1] in layer]
    assert not whole, f"prefill_chunk makes whole K/V layers: {whole}"


def test_serve_step_reads_kv_in_place(step_programs):
    """The decode kernel reads the stacked cache in place: no op of the
    step makes one layer of K/V (a slice for the custom call's operand, or
    its ``(B*S, Hk*D)`` relayout), no ``(B*S, Hk)`` scale pool, and no
    relayout of a whole scale stack around a layer's write."""
    progs, cache = step_programs
    glob = cache["global"]
    layer = {glob[n].size // STEP_LAYERS for n in ("k", "v")}
    shapes = _entry_shapes(progs["serve_step"], _MAKES)
    slices = [c for c in shapes if c[0] == "s8" and math.prod(c[1]) in layer]
    assert not slices, f"serve_step makes whole K/V layers: {slices}"
    pool = (SLOTS * MAX_SEQ, _cfg().num_kv_heads)
    pools = [c for c in shapes if c[0] == "f32" and c[1] == pool]
    assert not pools, f"serve_step makes (B*S, Hk) scale pools: {pools}"
    stack = tuple(glob["k_scale"].shape)
    relaid = [c for c in _entry_shapes(progs["serve_step"], "copy")
              if c == ("f32", stack)]
    assert not relaid, f"serve_step relays whole scale stacks: {relaid}"


def test_serve_step_donates_cache(step_programs):
    progs, cache = step_programs
    alias = progs["serve_step"].memory_analysis().alias_size_in_bytes
    assert alias >= kvc.cache_bytes(cache), (alias, kvc.cache_bytes(cache))
