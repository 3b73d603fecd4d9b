"""Platform selection (utils/runtime.py): the one rule every entry
point goes through — ``cpu`` forces the CPU, ``tpu`` raises without a
chip, unset takes JAX's default — and where the compile cache lives.
conftest itself exercises force_virtual_cpu for real (it is how this
very suite runs on the virtual 8-CPU mesh).
"""
import os
import subprocess
import sys

import jax
import pytest

from reporter_tpu.utils import runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_force_virtual_cpu_idempotent():
    # conftest already forced cpu; calling again must be a safe no-op
    runtime.force_virtual_cpu(8)
    runtime.force_virtual_cpu()
    assert jax.default_backend() == "cpu"
    assert len(jax.devices()) == 8


def test_force_virtual_cpu_pins_config_and_env():
    runtime.force_virtual_cpu()
    assert jax.config.jax_platforms == "cpu"
    assert os.environ["JAX_PLATFORMS"] == "cpu"


def test_ensure_backend_env_cpu(monkeypatch):
    monkeypatch.setattr(runtime, "_decided", None)
    monkeypatch.setenv(runtime.ENV_PLATFORM, "cpu")
    assert runtime.ensure_backend() == "cpu"


def test_ensure_backend_caches_decision(monkeypatch):
    monkeypatch.setattr(runtime, "_decided", "cpu")
    # cached decision short-circuits before any env read
    monkeypatch.setenv(runtime.ENV_PLATFORM, "definitely-invalid")
    assert runtime.ensure_backend() == "cpu"


@pytest.mark.parametrize("value", ["gpu3000", "auto", "accel"])
def test_ensure_backend_rejects_unknown(monkeypatch, value):
    # auto/accel belonged to the removed probe: they are errors now
    monkeypatch.setattr(runtime, "_decided", None)
    monkeypatch.setenv(runtime.ENV_PLATFORM, value)
    with pytest.raises(ValueError):
        runtime.ensure_backend()


def test_ensure_backend_unset_adopts_default(monkeypatch):
    # unset takes JAX's own default platform (the CPU here)
    monkeypatch.setattr(runtime, "_decided", None)
    monkeypatch.delenv(runtime.ENV_PLATFORM, raising=False)
    assert runtime.ensure_backend() == "cpu"


def test_ensure_backend_tpu_raises_without_chip(monkeypatch):
    monkeypatch.setattr(runtime, "_decided", None)
    monkeypatch.setenv(runtime.ENV_PLATFORM, "tpu")
    with pytest.raises(RuntimeError, match="no TPU"):
        runtime.ensure_backend()
    assert runtime._decided is None  # nothing pinned on failure


def _record_config(monkeypatch):
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, val: calls.__setitem__(name, val))
    return calls


def test_compile_cache_env_dir_wins(monkeypatch, tmp_path):
    calls = _record_config(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert calls == {}  # JAX reads the env var itself: nothing set


def test_compile_cache_default_in_checkout(monkeypatch):
    calls = _record_config(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert runtime.DEFAULT_COMPILE_CACHE == want
    assert runtime.enable_compile_cache() == want
    assert calls["jax_compilation_cache_dir"] == want
    assert os.path.isdir(want)


def _fresh(code: str, **env_over) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS",
                        runtime.ENV_PLATFORM, runtime.ENV_VIRTUAL_DEVICES)}
    env["PYTHONPATH"] = REPO
    env.update(env_over)
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)


def test_fresh_process_platform_cpu():
    # REPORTER_TPU_PLATFORM=cpu alone brings a clean interpreter up on
    # the CPU, as the virtual mesh it names
    proc = _fresh(
        "from reporter_tpu.utils.runtime import ensure_backend\n"
        "assert ensure_backend() == 'cpu'\n"
        "import jax\n"
        "assert jax.default_backend() == 'cpu'\n"
        "assert len(jax.devices()) == 2\n"
        "print('ok')\n",
        REPORTER_TPU_PLATFORM="cpu", REPORTER_TPU_VIRTUAL_DEVICES="2")
    assert proc.returncode == 0, proc.stderr[-500:]
    assert proc.stdout.strip().endswith("ok")


def test_fresh_process_force_virtual_cpu():
    proc = _fresh(
        "from reporter_tpu.utils.runtime import force_virtual_cpu\n"
        "force_virtual_cpu(4)\n"
        "import jax\n"
        "assert jax.default_backend() == 'cpu'\n"
        "assert len(jax.devices()) == 4\n"
        "print('ok')\n")
    assert proc.returncode == 0, proc.stderr[-500:]
    assert proc.stdout.strip().endswith("ok")
