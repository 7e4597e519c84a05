"""chip_smoke.py rehearsed on the CPU at a tiny width.

The phases run here with the Pallas kernels in interpret mode, so the
script's own check for a compiled TPU kernel would refuse them; main()
itself must refuse to run off a TPU.
"""
import importlib.util
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
TINY = dict(n_features=64, n_hcu=8, n_mcu=16, fan_in=16, n_train=512,
            n_test=128, batch=64)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def test_phases_at_tiny_width(smoke):
    sizes = smoke.Sizes(**TINY)
    data = smoke.make_data(sizes, 0)
    a = smoke.phase_a(sizes, data, 0)
    assert a["accuracy"] > 2.0 / smoke.N_CLASSES
    assert a["reference"]["cij"].shape == (2 * sizes.n_features,
                                           sizes.n_hcu * sizes.n_mcu)
    b = smoke.phase_b(sizes, data, 0, a["reference"])
    assert set(b["errors"]) == {"ci", "cj", "cij", "w"}
    # Interpret mode: the epoch program holds no TPU kernel.
    assert "tpu_custom_call" not in b["epoch_hlo"]


def test_four_chip_phase_on_four_host_devices():
    from tests.test_distributed import run_with_devices

    out = run_with_devices(f"""
        import sys
        sys.path.insert(0, {str(ROOT)!r})
        import chip_smoke as cs
        sizes = cs.Sizes(**{TINY!r})
        errs = cs.four_chip_phase(sizes, cs.make_data(sizes, 0), 0)
        print(sorted(errs))
    """, n=4)
    assert "[(2, 2), (4, 1)]" in out


def test_main_refuses_off_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as e:
        smoke.main([])
    assert "no TPU" in str(e.value.code)
    assert capsys.readouterr().out == ""
