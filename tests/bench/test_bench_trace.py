"""The trace reduction on a small trace recorded on one TPU v5e
(``data/``): 0.29 s of a ``phi4-chat-int8`` window, three and a bit decode
steps and a prefill chunk, cut down as ``trim_xplane.py`` does.  The
recording predates the benchmark's ``bench.window`` span, so the cut added
one over the recorded ``bench.step`` spans of the third to the sixth step.
What the reduction reads was checked by hand against the whole trace's
planes and lines."""

from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"
XPLANE = DATA / "phi4-chat-int8.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce(str(XPLANE))


def test_window_and_device(reduced):
    assert len(reduced.devices) == 1 and reduced.devices[0].index == 0
    assert 0.2 < reduced.window_s < 0.4
    dev = reduced.devices[0]
    assert 0 < dev.busy_ns <= reduced.window_s * 1e9


def test_step_programs_and_kernels(reduced):
    dev = reduced.devices[0]
    assert dev.module_calls(tr.SERVE_STEP) >= 3
    per_step = dev.module_ns(tr.SERVE_STEP) / dev.module_calls(tr.SERVE_STEP)
    assert 5e6 < per_step < 2e8  # ns: a decode step of the 3.8 B model
    # one decode attend kernel per layer in every decode step
    assert dev.kernel_calls == 32 * dev.module_calls(tr.SERVE_STEP)
    assert 0 < dev.kernel_ns < dev.module_ns(tr.SERVE_STEP)


def test_gaps_cover_the_idle_time(reduced):
    dev = reduced.devices[0]
    idle = sum(b - a for a, b in dev.gaps)
    assert idle == pytest.approx(reduced.window_s * 1e9 - dev.busy_ns,
                                 rel=1e-9)
    assert all(b > a for a, b in dev.gaps)


def test_breakdown(reduced):
    out = tr.breakdown(reduced)
    assert 0 < len(out["device_ops"]) <= 10
    assert 0 < len(out["idle_gaps"]) <= 10
    secs = [s for _, s in out["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    labels = {name for name, _ in out["idle_gaps"]}
    assert labels <= {"host inside Scheduler.step", "host in client code",
                      "idle: no request pending",
                      "host in event loop between steps"}
