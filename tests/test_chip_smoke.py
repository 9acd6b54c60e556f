"""chip_smoke.py: its CPU rehearsal passes, and off the TPU (or away from
the repo) it fails without printing a result line."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _run(args, cwd=ROOT, script=SCRIPT, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, script, *args], cwd=cwd, env=env,
                         capture_output=True, text=True, timeout=timeout)
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    return out.returncode, lines[-1] if lines else ""


def _is_ok_line(line):
    try:
        return json.loads(line).get("ok") is True
    except ValueError:
        return False


@pytest.mark.parametrize("chips", [1, 4])
def test_rehearsal_passes_on_cpu(chips):
    rc, last = _run(["--rehearse", "--chips", str(chips)])
    assert rc == 0, last
    res = json.loads(last)
    assert res["rehearsal"] == "passed" and not _is_ok_line(last)
    assert res["device"] == {"platform": "cpu", "kind": "cpu", "count": chips}


def test_fails_without_a_tpu():
    rc, last = _run([])
    assert rc != 0
    assert not _is_ok_line(last)


def test_fails_outside_the_repo(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    rc, last = _run([], cwd=tmp_path, script=str(lone))
    assert rc != 0
    assert not _is_ok_line(last)
