"""``chip_smoke.py`` must refuse to pass without a TPU: a CPU run is
never a pass on the chip, so the script has no CPU mode."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "jax found no TPU" in proc.stderr
    assert "platform=cpu" in proc.stdout
    # No result line: nothing on stdout can be read as a pass.
    assert '"ok"' not in proc.stdout
