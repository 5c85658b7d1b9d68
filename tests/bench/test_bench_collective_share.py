"""``collective_share``: device time of collective ops over busy time, on
the device where it is largest, from the trace reduction's per-op times."""

import types
from pathlib import Path

import pytest

from bench import trace as tr
from bench.spec import CODE, load_module

metric = load_module(CODE / "metrics" / "collective_share.py")
DATA = Path(__file__).resolve().parent / "data"


def run_of(*devices):
    trace = tr.Trace(window_ns=(0.0, 1e9), devices=list(devices), host={})
    return types.SimpleNamespace(trace=trace)


def device(index, busy_ns, ops):
    dev = tr.Device(index=index, busy_ns=busy_ns)
    dev.ops.update(ops)
    return dev


@pytest.mark.parametrize("name", [
    "all-reduce", "all-reduce-start", "all-reduce-done", "all-gather",
    "all-gather-start", "reduce-scatter", "collective-permute-done",
    "all-to-all"])
def test_each_collective_kind_counts(name):
    run = run_of(device(0, 1000.0, {name: 250.0, "fusion": 750.0}))
    assert metric.read(run) == pytest.approx(25.0)


def test_other_ops_do_not_count():
    run = run_of(device(0, 1000.0, {"fusion": 600.0, "copy": 300.0,
                                    "convolution": 100.0}))
    assert metric.read(run) == 0.0


def test_the_device_with_the_largest_share():
    run = run_of(device(0, 1000.0, {"all-reduce": 100.0}),
                 device(1, 500.0, {"all-reduce": 100.0}),
                 device(2, 0.0, {}))
    assert metric.read(run) == pytest.approx(20.0)


def test_nothing_without_a_trace():
    assert metric.read(types.SimpleNamespace(trace=None)) is None


def test_one_chip_trace_reads_zero():
    run = types.SimpleNamespace(
        trace=tr.reduce(str(DATA / "phi4-chat-int8.xplane.pb")))
    assert metric.read(run) == 0.0
