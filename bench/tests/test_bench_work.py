"""The work function at the paper's MNIST and STL-10 widths."""
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import peaks, work  # noqa: E402

V5E = peaks.peaks("TPU v5 lite")


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


# Worked by hand from the shapes (F coded inputs, H hidden units, B batch):
# bytes = 4 * (B*F + 2*F*H + F*H/N_HCU + 2*(F + H)),
# flops = 2*B*fan_in_units*H + 2*B*F*H + 7*F*H + 5*F*H/N_HCU.
@pytest.mark.parametrize("name, flops, nbytes, least_s", [
    ("stl10-20x150",
     2 * 128 * 2048 * 3000 + 2 * 128 * 55296 * 3000 + 7 * 55296 * 3000
     + 5 * 55296 * 3000 // 20,
     4 * (128 * 55296 + 2 * 55296 * 3000 + 55296 * 3000 // 20
          + 2 * (55296 + 3000)),
     1.70e-3),
    ("mnist-30x100",
     2 * 128 * 156 * 3000 + 2 * 128 * 1568 * 3000 + 7 * 1568 * 3000
     + 5 * 1568 * 3000 // 30,
     4 * (128 * 1568 + 2 * 1568 * 3000 + 1568 * 3000 // 30
          + 2 * (1568 + 3000)),
     48e-6),
])
def test_hidden_batch_work(name, flops, nbytes, least_s):
    w = work.hidden_batch(config(name))
    assert (w.flops, w.bytes, w.batch) == (flops, nbytes, 128)
    assert w.bytes / V5E.hbm_bw > w.flops / V5E.bf16_flops  # memory-bound
    assert w.least_seconds(V5E) == pytest.approx(least_s, rel=0.01)


def test_hand_worked_totals():
    """The totals quoted in PERF.md: about 1.39 GB and 45 GFLOP per STL-10
    batch, 39 MB and 1.3 GFLOP per MNIST batch."""
    stl = work.hidden_batch(config("stl10-20x150"))
    mnist = work.hidden_batch(config("mnist-30x100"))
    assert stl.bytes == pytest.approx(1.389e9, rel=1e-3)
    assert stl.flops == pytest.approx(45.2e9, rel=1e-2)
    assert mnist.bytes == pytest.approx(39.1e6, rel=1e-2)
    assert mnist.flops == pytest.approx(1.36e9, rel=1e-2)


def test_no_weight_traffic_counted():
    """w is derivable from the marginals and the mask: doubling nothing but
    the stored-weight size would change nothing, so no term uses it.  The
    count is the marginals' and the inputs' alone: dropping C_ij's two
    passes leaves a remainder far under one F x H pass."""
    cfg = config("stl10-20x150")
    f, h = 55296, 3000
    w = work.hidden_batch(cfg)
    rest = w.bytes - 4 * 2 * f * h - 4 * f * h // 20
    assert rest < 4 * f * h / 5


def test_dp4_counts_the_global_batch_once():
    one = work.hidden_batch(config("stl10-20x150"))
    dp4 = work.hidden_batch(config("stl10-20x150-dp4"))
    assert dp4.batch == 512
    # Same per-sample forward and outer product; the per-batch elementwise
    # update is counted once per global batch, not once per chip.
    assert dp4.flops_per_sample < one.flops_per_sample
    assert dp4.flops == pytest.approx(4 * one.flops, rel=0.05)


def test_unknown_device_has_no_peak():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("cpu")
