"""The system under test, built for one cell: the program's model config,
its KV cache layout and sharding rules, and the benchmark's own weights.

Only the program's public serving entry points are used here
(``Scheduler``, ``AsyncServer``, ``layout_for``, the serving placement);
the weights are made by the benchmark, so the reference takes nothing
the program has made.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp


def model_config(cell):
    """The program's ModelConfig for the cell, checked against its file."""
    from repro.configs import get_config

    from bench.spec import check_model

    cfg = get_config(cell.config["registry"],
                     smoke=cell.config.get("variant") == "smoke")
    check_model(cfg, cell.config)
    return cfg


def layout(cell, cfg):
    from repro.serving import kv_cache as kvc

    s = cell.serving
    return kvc.layout_for(cfg, int(s["slots"]), int(s["max_seq"]),
                          kv_format=s["kv_format"], layout=s["kv_layout"])


def seed_key(seed: int):
    """A threefry key from any whole number, seeds past 2**31 included."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def weight_shapes(cfg):
    from repro.models import model_zoo

    return jax.eval_shape(lambda k: model_zoo.init_params(k, cfg),
                          jax.random.key(0))


def weight_shardings(cfg, rules, shapes):
    """The program's serving placement for weights of these shapes."""
    from repro.models import model_zoo
    from repro.serving import sharded as shd

    if rules.mesh is None:
        return None
    return shd.param_shardings(shapes, model_zoo.param_specs(cfg), rules)


def make_weights(cell, cfg, rules, seed: int):
    """Random weights from ``seed``, made on the device by one jitted call,
    in the type they are served in and in the serving placement."""
    ref = cell.reference()
    shapes = weight_shapes(cfg)
    init = jax.jit(lambda key: ref.init_weights(key, shapes),
                   out_shardings=weight_shardings(cfg, rules, shapes))
    return init(seed_key(seed))
