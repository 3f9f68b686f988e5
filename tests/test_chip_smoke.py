"""chip_smoke.py off the chip: it must refuse, and its CPU reference
losses must be what the CPU computes."""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120, env=env)
    assert r.returncode != 0
    assert "found no TPU" in r.stderr
    assert '"ok"' not in r.stdout


@pytest.mark.slow  # ~2 min: seven full-width BERT-base steps on the CPU
def test_cpu_reference_losses():
    """Regenerates ``chip_smoke.CPU_F32_LOSSES``: the same model, seed,
    batch and optimizer in f32 on the CPU, with no kernel."""
    import jax
    sys.path.insert(0, REPO)
    import chip_smoke
    engine, batch = chip_smoke.build_trainer(
        [jax.devices()[0]], {"dp": 1}, amp_dtype=None)
    losses = [float(engine.step(batch)) for _ in range(7)]
    np.testing.assert_allclose(losses, chip_smoke.CPU_F32_LOSSES,
                               rtol=1e-4)
