"""The epoch-input readers: the program's train.gather and train.upload
spans, alone and against device 0's idle time."""
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from bench import devtrace, spec  # noqa: E402

# Device 0 is busy on [10, 50], [70, 90] and [120, 160].  The first gather
# overlaps device work on [20, 50] and waits on [50, 60]; the second gather
# and the upload (which overlap) wait on [95, 120] and share [120, 130]
# with device work.
TRACE = {
    "devices": {
        0: [[10, 40, "fusion.1"], [30, 50, "fusion.2"],
            [70, 90, "fusion.1"], [120, 160, "fusion.1"]],
    },
    "host": [[0, 200, "bench.call"], [20, 60, "train.gather"],
             [95, 110, "train.gather"], [105, 130, "train.upload"],
             [106, 125, "TransferToDevice"]],
}


def _read(name, span):
    return spec.load_module(HERE.parent / "metrics" / f"{name}.py").read(span)


def _span(trace):
    return devtrace.Span(trace, 0, 200, chips=1, batches=4, samples=32,
                         host_s=0.0, work=None, device_kind="TPU v5 lite")


@pytest.mark.parametrize("name,want", [
    ("epoch_gather_share", 27.5),  # [20, 60] + [95, 110] of 200
    ("epoch_upload_share", 12.5),  # [105, 130]
    # idle [50, 60] + [95, 120]: the gather's [20, 50] under device work
    # and the upload's [120, 130] are left out
    ("input_idle_share", 17.5),
])
def test_readers_on_the_synthetic_span(name, want):
    assert _read(name, _span(TRACE)) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "epoch_gather_share", "epoch_upload_share", "input_idle_share"])
def test_readers_are_silent_without_the_program_spans(name):
    bare = {"devices": TRACE["devices"],
            "host": [ev for ev in TRACE["host"]
                     if not ev[2].startswith("train.")]}
    assert _read(name, _span(bare)) is None


@pytest.mark.parametrize("name", [
    "epoch_gather_share", "epoch_upload_share", "input_idle_share"])
def test_readers_are_silent_without_device_ops(name):
    assert _read(name, _span({"devices": {0: []}, "host": TRACE["host"]})) is None
