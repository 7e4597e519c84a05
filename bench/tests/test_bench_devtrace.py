"""The trace reducer: busy union, idle share, collective time, longest gaps."""
import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from bench import devtrace, work  # noqa: E402

# Device 0: two overlapping ops, one collective, idle gaps of 10, 20 and 30.
TRACE = {
    "devices": {
        0: [[10, 40, "fusion.1"], [30, 50, "fusion.2"],
            [70, 90, "all-reduce.3"], [120, 160, "fusion.1"]],
        1: [[0, 100, "fusion.1"]],
    },
    "host": [[0, 200, "bench.call"], [45, 75, "TransferToDevice"],
             [90, 125, "$epoch_engine.py:71 stack_epoch"],
             [92, 118, "np.take"]],
}


def test_union_busy_and_gaps():
    dev0 = TRACE["devices"][0]
    assert devtrace.union(dev0) == [[10, 50], [70, 90], [120, 160]]
    assert devtrace.busy_ns(dev0) == 100
    assert devtrace.gaps(dev0, 0, 200) == [[0, 10], [50, 70], [90, 120],
                                           [160, 200]]
    assert devtrace.collective_ns(dev0) == 20


def test_idle_gaps_are_longest_first_and_labelled_by_the_host():
    got = devtrace.idle_gaps(TRACE["devices"][0], TRACE["host"], 0, 200, n=3)
    assert got == [["bench.call", 40e-9], ["np.take", 30e-9],
                   ["TransferToDevice", 20e-9]]


def test_top_ops_sum_by_name():
    assert devtrace.top_ops(TRACE["devices"][0], n=2) == [
        ["fusion.1", 70e-9], ["fusion.2", 20e-9]]


def test_span_clips_to_the_window_and_averages_chips():
    span = devtrace.Span(TRACE, 20, 120, chips=2, batches=4, samples=512,
                         host_s=0.0, work=None, device_kind="TPU v5 lite")
    assert span.window_s == pytest.approx(100e-9)
    assert span.busy_s(0) == pytest.approx(50e-9)  # [20,50] + [70,90]
    assert span.busy_s(1) == pytest.approx(80e-9)
    assert span.mean_busy_s == pytest.approx(65e-9)
    assert span.peak.hbm_bw == 819e9


def _metric(name):
    from bench import spec

    return spec.load_module(HERE.parent / "metrics" / f"{name}.py").read


def test_metrics_on_the_synthetic_span():
    w = work.Work(flops=1000, bytes=819 * 4, batch=8)  # 4 ns at HBM peak
    span = devtrace.Span(TRACE, 0, 200, chips=1, batches=4, samples=32,
                         host_s=50e-9, work=w, device_kind="TPU v5 lite")
    assert _metric("device_idle_share")(span) == pytest.approx(50.0)
    assert _metric("fit_host_share")(span) == pytest.approx(25.0)
    # least 4 ns a batch against 100 ns / 4 batches of busy time
    assert _metric("hidden_batch_roofline")(span) == pytest.approx(16.0)
    assert _metric("collective_share")(span) is None  # one chip
    two = devtrace.Span(TRACE, 0, 200, chips=2, batches=4, samples=32,
                        host_s=0.0, work=w, device_kind="TPU v5 lite")
    assert _metric("collective_share")(two) == pytest.approx(20.0)


def test_recorded_trace():
    """6 ms of one STL-10 epoch scan recorded on one TPU v5e (stl10-hidden):
    the loop op spans the whole slice, and its body's ops nest inside it."""
    trace = json.loads((HERE / "data" / "trace_stl10-hidden.json").read_text())
    dev0 = trace["devices"]["0"]
    lo, hi = devtrace.annotated_window(trace["host"], "bench.call")
    assert devtrace.busy_ns(dev0) == hi - lo == 6_000_000
    assert devtrace.gaps(dev0, lo, hi) == []
    assert devtrace.collective_ns(dev0) == 0.0
    top = devtrace.top_ops(dev0, n=3)
    assert [name for name, _ in top] == [
        "subtract_multiply_fusion.3", "add_multiply_fusion.2", "reshape.63"]
    assert top[0][1] == pytest.approx(2.999996e-3)
    # The loop and the conditional are left out, so the listed ops' time
    # stays within the slice.
    assert sum(t for _, t in devtrace.top_ops(dev0, n=100)) <= 6e-3
