"""The load generator: its lengths follow the traffic file, every seed does
the same work in another order, and a seed always plans the same run."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import loadgen

REPO = Path(__file__).resolve().parents[2]

CHAT = json.loads((REPO / "bench" / "traffic" / "chat.json").read_text())
# long documents: uniform lengths, an open loop
LONGDOC = {"loop": "open", "rate_per_s": 0.5, "strata": 8, "order_seed": 1,
           "prompt": {"dist": "uniform", "min": 1536, "max": 3072},
           "output": {"dist": "uniform", "min": 256, "max": 768}}
BIG_SEED = 2 ** 31 + 977  # seeds may pass 32 signed bits


def _lengths(reqs):
    return ([len(r.prompt) for r in reqs], [r.max_new for r in reqs])


@pytest.mark.parametrize("traffic", [CHAT, LONGDOC], ids=["chat", "longdoc"])
def test_same_seed_same_plan(traffic):
    a = loadgen.plan(traffic, BIG_SEED, 200064, 10.0, 20.0, 4096)
    b = loadgen.plan(traffic, BIG_SEED, 200064, 10.0, 20.0, 4096)
    assert _lengths(a) == _lengths(b)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [r.due_s for r in a] == [r.due_s for r in b]


@pytest.mark.parametrize("traffic", [CHAT, LONGDOC], ids=["chat", "longdoc"])
def test_seeds_share_every_block_of_lengths(traffic):
    k = traffic["strata"]
    a = loadgen.plan(traffic, 1, 200064, 10.0, 60.0, 4096)
    b = loadgen.plan(traffic, BIG_SEED, 200064, 10.0, 60.0, 4096)
    pa, oa = _lengths(a)
    pb, ob = _lengths(b)
    # the same lengths at the same times for every seed, other tokens
    assert (pa, oa) == (pb, ob)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert not np.array_equal(a[0].prompt[:16], b[0].prompt[:16])
    # the traffic file's order_seed orders the same set otherwise
    c = loadgen.plan(dict(traffic, order_seed=2), 1, 200064, 10.0, 60.0, 4096)
    pc, oc = _lengths(c)
    w = round(traffic["rate_per_s"] * 10.0)
    assert pc != pa
    for lo, hi in ((0, w), (w, len(a))):  # the warm part, the window
        assert sorted(pa[lo:hi]) == sorted(pc[lo:hi])
        assert sorted(oa[lo:hi]) == sorted(oc[lo:hi])
    # every run of k requests in the window holds one length of each band
    win = np.asarray(ob[w:])
    bands = np.array_split(np.sort(win), k)
    for lo in range(0, len(win) - k + 1, k):
        for band, v in zip(bands, np.sort(win[lo:lo + k])):
            assert band[0] <= v <= band[-1]


def test_stratified_order_spreads_each_band():
    rng = np.random.default_rng(3)
    out = loadgen.stratified(rng, np.arange(38), 8)  # six bands of 5, two of 4
    assert sorted(out) == list(range(38))
    band = np.concatenate([[b] * len(v) for b, v in
                           enumerate(np.array_split(np.arange(38), 8))])
    for lo in range(0, 32, 8):
        assert sorted(band[out[lo:lo + 8]]) == list(range(8))
    assert sorted(band[out[32:]]) == list(range(6))


def test_chat_lengths_follow_the_file():
    reqs = loadgen.plan(CHAT, 5, 200064, 10.0, 100.0, 2048)
    prompts, outputs = map(np.asarray, _lengths(reqs))
    assert prompts.min() >= 64 and prompts.max() <= 1536
    assert outputs.min() >= 16 and outputs.max() <= 512
    assert abs(np.median(prompts) - 384) <= 0.05 * 384
    assert abs(np.median(outputs) - 128) <= 0.05 * 128
    # lognormal sigma 0.8: a tenth of prompts above 384 * e^(0.8 * 1.28)
    assert np.mean(prompts > 384 * np.exp(0.8 * 1.2816)) == pytest.approx(
        0.1, abs=0.04)


def test_open_loop_rate():
    reqs = loadgen.plan(CHAT, 9, 200064, 10.0, 60.0, 2048)
    due = np.asarray([r.due_s for r in reqs])
    n_warm = round(CHAT["rate_per_s"] * 10.0)
    assert len(reqs) == n_warm + round(CHAT["rate_per_s"] * 60.0)
    assert np.all(np.diff(due) >= 0)
    assert due[0] == 0.0 and due[n_warm] == 10.0 and due[-1] < 70.0
    assert np.mean(np.diff(due[n_warm:])) == pytest.approx(
        1 / CHAT["rate_per_s"], rel=0.1)


def test_uniform_lengths_follow_the_file():
    reqs = loadgen.plan(LONGDOC, 3, 102400, 0.0, 64.0, 4096)
    prompts, outputs = map(np.asarray, _lengths(reqs))
    assert prompts.min() >= 1536 and prompts.max() <= 3072
    assert outputs.min() >= 256 and outputs.max() <= 768
    # the window takes the quantiles (i + 0.5) / n of the uniform range
    assert np.mean(prompts) == pytest.approx((1536 + 3073) / 2, rel=0.01)
    assert np.mean(outputs) == pytest.approx((256 + 769) / 2, rel=0.01)


def test_traffic_longer_than_the_cache_is_refused():
    with pytest.raises(ValueError, match="positions"):
        loadgen.plan(LONGDOC, 3, 102400, 0.0, 10.0, 2048)
