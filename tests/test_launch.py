"""Launcher contracts: exit codes on failed work, and where the persistent
compilation cache goes."""

import math
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache, serve, train
from repro.serve.guard import FAILED

jax.config.update("jax_platform_name", "cpu")

SRC = Path(__file__).resolve().parents[1] / "src"

SERVE_ARGS = ["--arch", "qwen3-0.6b", "--smoke", "--batch", "2",
              "--cache-len", "32", "--n-requests", "2", "--max-new", "2"]


@pytest.fixture
def cache_dir_restored():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def _fake_serve(status):
    def serve_requests(engine, reqs):
        return [[0] * r.max_new for r in reqs], [status] * len(reqs)
    return serve_requests


def test_serve_exits_nonzero_when_a_request_fails(monkeypatch,
                                                  cache_dir_restored):
    monkeypatch.setattr(serve, "serve_requests", _fake_serve(FAILED))
    with pytest.raises(SystemExit) as exc:
        serve.main(SERVE_ARGS)
    assert "did not finish" in str(exc.value.code)


@pytest.mark.parametrize("extra", [["--deadline-ms", "1e6"],
                                   ["--max-queue", "8"]])
def test_serve_tolerates_losses_it_was_asked_for(monkeypatch,
                                                 cache_dir_restored, extra):
    monkeypatch.setattr(serve, "serve_requests", _fake_serve(FAILED))
    serve.main(SERVE_ARGS + extra)


def test_serve_finishes_cleanly(cache_dir_restored, capsys):
    serve.main(SERVE_ARGS)
    assert "request 1:" in capsys.readouterr().out


class _Driver:
    def __init__(self, losses):
        self.metrics_log = [{"step": i, "loss": v, "dt": 0.0}
                            for i, v in enumerate(losses)]
        self.restarts = 0
        self.watchdog = type("W", (), {"events": []})()

    def run(self, state, n_steps):
        return state


@pytest.mark.parametrize("losses,fails", [([2.0, math.nan], True),
                                          ([2.0, 1.5], False)])
def test_train_exits_nonzero_on_nonfinite_loss(monkeypatch,
                                               cache_dir_restored, losses,
                                               fails):
    monkeypatch.setattr(train, "build_trainer",
                        lambda *a, **kw: (_Driver(losses), None))
    argv = ["--arch", "qwen3-0.6b", "--smoke", "--steps", "2"]
    if fails:
        with pytest.raises(SystemExit) as exc:
            train.main(argv)
        assert "non-finite loss at steps [1]" in str(exc.value.code)
    else:
        train.main(argv)


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_dir_restored):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.DEFAULT_CACHE_DIR)
    assert compile_cache.DEFAULT_CACHE_DIR.parent == (
        compile_cache.Path(train.__file__).resolve().parents[3])
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_left_to_environment(monkeypatch, cache_dir_restored,
                                           tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the code sets no directory
    assert jax.config.jax_compilation_cache_dir is None


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_files_land_in_one_place(tmp_path, env_set):
    """A compile in a fresh process writes its cache entry where the rule
    says, and nowhere else (the default directory is moved under
    ``tmp_path`` here so the test leaves the checkout alone)."""
    env_dir, default_dir = tmp_path / "env", tmp_path / "default"
    code = (
        "import jax, jax.numpy as jnp\n"
        "from pathlib import Path\n"
        "from repro.launch import compile_cache\n"
        f"compile_cache.DEFAULT_CACHE_DIR = Path({str(default_dir)!r})\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "compile_cache.enable_compile_cache()\n"
        "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(3)).block_until_ready()\n")
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(SRC))
    if env_set:
        env[compile_cache.ENV_VAR] = str(env_dir)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    used, unused = ((env_dir, default_dir) if env_set
                    else (default_dir, env_dir))
    assert any(used.iterdir())
    assert not unused.exists()
