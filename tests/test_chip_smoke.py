"""chip_smoke.py's refusals: off the chip, alone, or with a broken decode
it exits non-zero and never prints the ok line.

The smoke rebuilds the native library with ``make -B``; the fault run
works on a copy of the package so that rebuild never touches the
library other test workers have loaded.
"""
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(cwd, *args, env=None, timeout=240):
    return subprocess.run([sys.executable, "chip_smoke.py", *args], cwd=cwd,
                          env={**os.environ, **(env or {})},
                          capture_output=True, text=True, timeout=timeout)


def _no_ok_line(proc):
    return '"ok"' not in proc.stdout


def test_no_chip_exits_nonzero():
    """The platform is checked before the native rebuild: nothing in
    the checkout changes."""
    proc = _run(REPO, env={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode not in (0, 3), proc.stderr[-2000:]
    assert _no_ok_line(proc)
    assert "tpu" in proc.stderr


def test_alone_in_a_directory_exits_nonzero(tmp_path):
    shutil.copy(SMOKE, tmp_path)
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert _no_ok_line(proc)
    assert proc.stdout == ""


def test_broken_decode_fails_the_smoke(tmp_path):
    """Every chunk's device decode raises: the breaker serves them from
    the numpy oracle with 200s, and the smoke fails on the fallback
    counters of the warm pass."""
    shutil.copy(SMOKE, tmp_path)
    shutil.copytree(os.path.join(REPO, "reporter_tpu"),
                    tmp_path / "reporter_tpu",
                    ignore=shutil.ignore_patterns("__pycache__", "*.so"))
    proc = _run(tmp_path, "--rehearse", "--rows", "30", "--traces", "64",
                env={"JAX_PLATFORMS": "cpu",
                     "REPORTER_TPU_FAULTS": "decode.dispatch=error"})
    assert proc.returncode == 1, (proc.stdout[-2000:], proc.stderr[-2000:])
    assert _no_ok_line(proc)
    assert "smoke FAILED: serve warm pass: fallback counters non-zero" \
        in proc.stderr
    assert "matcher.circuit.decode.errors" in proc.stderr
