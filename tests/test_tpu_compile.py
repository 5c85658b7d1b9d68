"""The served decode kernels compile for a TPU v5e — no chip needed.

Each test lowers one Pallas kernel at phi4-mini-3.8b's published attention
widths (8 kv heads, GQA group 3, head_dim 128) and the pool sizes
``chip_smoke.py`` serves (8 slots, max_seq 1024) and compiles it for a
described ``v5e:2x2`` with the TPU compiler installed here.  That is what
interpret mode cannot show: Mosaic refuses block shapes off the (8, 128)
tiling and kernels that overrun the scoped VMEM.  Nothing runs.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.bgpp_paged_attend.kernel import bgpp_paged_attend_pallas
from repro.kernels.paged_flash_decode.kernel import paged_flash_decode_pallas
from repro.serving import kv_cache as kvc

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
    """(n_tok, page_size, pages_per_slot) of the pool the kernel sees: the
    paged layout's own pool, or the slot stack viewed as one page per slot."""
    if layout_kind == "slot":
        return SLOTS * MAX_SEQ, MAX_SEQ, 1
    lay = kvc.layout_for(_cfg(), SLOTS, MAX_SEQ, kv_format=fmt,
                         layout="paged", page_size=page_size)
    return lay.num_pages * page_size, page_size, lay.pages_per_slot


def _compile(f, *args):
    compiled = jax.jit(f).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("fmt", ["bf16", "int8"])
@pytest.mark.parametrize("layout_kind,page_size",
                         [("slot", None), ("paged", 8)])
def test_paged_flash_decode_compiles(one_chip, fmt, layout_kind, page_size):
    cfg = _cfg()
    Hk, D = cfg.num_kv_heads, cfg.head_dim
    g = cfg.num_heads // Hk
    n_tok, P, pp = _pool(layout_kind, fmt, page_size)
    dt = jnp.bfloat16 if fmt == "bf16" else jnp.int8
    args = [one_chip((SLOTS, Hk, g, D), jnp.float32),
            one_chip((n_tok, Hk, D), dt), one_chip((n_tok, Hk, D), dt),
            one_chip((SLOTS, pp), jnp.int32), one_chip((SLOTS,), jnp.int32)]
    if fmt == "int8":
        args += [one_chip((n_tok, Hk), jnp.float32)] * 2

    def f(q, k, v, table, pos, *scales):
        ks, vs = scales or (None, None)
        return paged_flash_decode_pallas(q, k, v, table, pos, page_size=P,
                                         k_scale=ks, v_scale=vs)

    _compile(f, *args)


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
