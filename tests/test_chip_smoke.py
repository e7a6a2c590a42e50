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


def _run_main(monkeypatch, capsys, tmp_path, kernels):
    """``chip_smoke.main()`` with the device and the phases stubbed."""
    import importlib.util

    from zookeeper_tpu.parallel import distributed

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py")
    )
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(smoke, "start", lambda: device)
    monkeypatch.setattr(
        distributed, "enable_compile_cache", lambda: str(tmp_path)
    )
    monkeypatch.setattr(smoke, "trainer_phase", lambda: {"steps": 6})
    monkeypatch.setattr(smoke, "server_phase", lambda: {"requests": 10})
    monkeypatch.setattr(smoke, "kernels_phase", kernels)
    code = smoke.main()
    return code, capsys.readouterr().out.strip().splitlines(), device


def test_chip_smoke_result_line_has_exactly_ok_and_device(
    monkeypatch, capsys, tmp_path
):
    import json

    code, lines, device = _run_main(monkeypatch, capsys, tmp_path, dict)
    assert code == 0
    # The last line is the result and nothing else: the phases' detail
    # is on the report line before it.
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    assert lines[-2].startswith("chip_smoke: report ")
    report = json.loads(lines[-2].split("report ", 1)[1])
    assert set(report["phases"]) == {"trainer", "server", "kernels"}
    assert report["claim"] is None


def test_chip_smoke_failed_phase_exits_nonzero(monkeypatch, capsys, tmp_path):
    import json

    def kernels():
        raise AssertionError("chip_smoke check failed: stub")

    code, lines, device = _run_main(monkeypatch, capsys, tmp_path, kernels)
    assert code == 1
    assert json.loads(lines[-1]) == {"ok": False, "device": device}
    report = json.loads(lines[-2].split("report ", 1)[1])
    assert report["phases"]["kernels"] == {"ok": False}
