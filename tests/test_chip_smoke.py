"""``chip_smoke.py`` off the chip: it must refuse to run without a TPU,
and each of its phases must pass at a small size on the CPU (kernels
interpreted), so that a chip call is spent on the chip, not on a wrong
path or argument.  The phases' full sizes run only on the TPU."""
import importlib.util
from pathlib import Path

import jax
import pytest

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu(smoke, capsys):
    if jax.default_backend() == "tpu":
        pytest.skip("a TPU is attached")
    with pytest.raises(SystemExit) as ei:
        smoke.main([])
    assert repr(jax.devices()[0].platform) in str(ei.value)
    assert capsys.readouterr().out == ""        # no result line


def test_fleet_solve_phase(smoke):
    facts = smoke.phase_fleet_solve(n_devices=3000, chunk_elements=1024)
    assert facts["max_dp_over_tol"] <= 1.0
    assert facts["E_participants_kernel"] > 0


def test_service_phase(smoke):
    facts = smoke.phase_service(n_devices=24, n_cells=3, n_requests=12,
                                n_reference=2)
    assert facts["n_responses"] == 12


def test_metro_tick_phase(smoke):
    facts = smoke.phase_metro_tick(n_cells=4, n_devices=16)
    assert facts["max_dobj"] <= smoke.A_ATOL


def test_training_phase(smoke):
    facts = smoke.phase_training(n_devices=8, n_rounds=2, n_train=256,
                                 n_test=64)
    assert facts["kernel_vs_jnp"]["params_rel_diff"] <= 2 * smoke.BF16_EPS


def test_check_fails_loudly(smoke):
    """A failed check raises; nothing turns it into a pass."""
    with pytest.raises(smoke.SmokeFailure, match="boom"), \
            smoke.phase("probe"):
        smoke.check(False, "boom")
