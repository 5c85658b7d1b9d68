"""The plain float32 reference computes the model the program serves:
against ``model_zoo.forward`` at smoke widths, on the benchmark's own
weights, for a tied (phi4) and an untied (deepseek) configuration."""


import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import system
from bench.spec import load_cell


@pytest.mark.parametrize("workload", ["smoke-open", "smoke-long"])
def test_reference_matches_model_zoo_forward(smoke_root, workload):
    from repro.models import model_zoo

    cell = load_cell(workload, smoke_root)
    ref = cell.reference()
    cfg = system.model_config(cell)
    w = ref.init_weights(system.seed_key(2 ** 31 + 3),
                         system.weight_shapes(cfg))
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, 40)
    want, _ = model_zoo.forward(w, cfg, {"tokens": jnp.asarray(toks)[None]},
                                block_q=16, block_k=16)
    got = ref.logits(w, ref.conf_items(cell.config), jnp.asarray(toks))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]),
                               rtol=2e-4, atol=2e-4)


def test_gaps_are_zero_on_the_references_own_argmax(smoke_root):
    cell = load_cell("smoke-open", smoke_root)
    ref = cell.reference()
    cfg = system.model_config(cell)
    w = ref.init_weights(system.seed_key(7), system.weight_shapes(cfg))
    prompt = np.arange(5, 25, dtype=np.int32)
    seq = list(prompt)
    for _ in range(6):  # greedy decode through the reference itself
        out = ref.logits(w, ref.conf_items(cell.config), jnp.asarray(seq))
        seq.append(int(jnp.argmax(out[-1])))
    served = np.asarray(seq[len(prompt):])
    cols = np.asarray([3, 17, 400])
    gaps, best, at_cols = ref.logit_gaps(w, cell.config, prompt, served,
                                         cols, seq_bucket=16, gen_bucket=4)
    assert gaps.shape == (6,) and np.all(gaps == 0)
    assert np.array_equal(best, served)
    full = ref.logits(w, ref.conf_items(cell.config), jnp.asarray(seq[:-1]))
    np.testing.assert_allclose(
        at_cols, np.asarray(full)[len(prompt) - 1:][:, cols], rtol=1e-5,
        atol=1e-5)
    wrong = served.copy()
    wrong[2] = (wrong[2] + 1) % cfg.vocab_size
    gaps, _, _ = ref.logit_gaps(w, cell.config, prompt, wrong)
    assert gaps[2] > 0 and np.all(gaps[:2] == 0)


def test_weights_follow_the_seed(smoke_root):
    cell = load_cell("smoke-long", smoke_root)
    ref = cell.reference()
    shapes = system.weight_shapes(system.model_config(cell))
    a = ref.init_weights(system.seed_key(2 ** 31 + 5), shapes)
    b = ref.init_weights(system.seed_key(2 ** 31 + 5), shapes)
    c = ref.init_weights(system.seed_key(5), shapes)
    for x, y, z in zip(*map(jax.tree.leaves, (a, b, c))):
        assert x.dtype == y.dtype and np.array_equal(x, y)
        assert not np.array_equal(x, z)
    emb = np.asarray(a["embed"], np.float32)
    assert np.std(emb) == pytest.approx(0.02, rel=0.1)
    wq = np.asarray(a["layers"]["attn"]["wq"], np.float32)
    assert np.std(wq) == pytest.approx(1 / np.sqrt(wq.shape[-2]), rel=0.1)
