"""chip_smoke.py runs on a TPU or not at all; its phases run on the CPU at a
tiny size so that the script's own logic is tested here."""
import importlib.util
import os
import shutil
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..")
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_to_run_without_a_tpu():
    out = _run(SCRIPT, ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout


def test_fails_without_the_repo(tmp_path):
    shutil.copy(SCRIPT, tmp_path)
    out = _run(str(tmp_path / "chip_smoke.py"), str(tmp_path))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_fit_checks_pass_at_a_tiny_size():
    smoke = _load()
    check = smoke.Checks()
    smoke.run_fits(0, check, n_train=600, n_test=200, budget=64)
    assert check.failed == []
