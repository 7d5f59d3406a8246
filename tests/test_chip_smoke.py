"""``chip_smoke.py``'s phases on the CPU at smoke sizes, and its refusal to
report success without a TPU."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.configs.registry import get_smoke  # noqa: E402

jax.config.update("jax_platform_name", "cpu")

SMOKE = get_smoke("qwen3-0.6b")


def _no_ok_line(text: str) -> bool:
    return '"ok": true' not in text


@pytest.mark.parametrize("k,shapes", [(8, ((4, 2), (2, 3))), (64, ((2, 3),))])
def test_kernel_phase_cpu(k, shapes):
    out = chip_smoke.kernel_phase(shapes, (8, 24), k=k, require_kernel=False)
    assert out["executables"] == 4 * 2 * len(shapes)
    assert out["max_rel_err"] < min(chip_smoke.KERNEL_RTOL.values())


def test_serve_phase_cpu():
    out = chip_smoke.serve_phase(SMOKE, batch=4, cache_len=64,
                                 prompt_buckets=(8, 16), n_requests=4,
                                 max_new=4, require_kernel=False)
    assert set(out) == {"xla", "pallas", "pallas-int8"}
    for entry in out.values():
        assert entry["statuses"] == ["FINISHED"]
        assert entry["tokens"] == 4 * 4
    assert out["pallas"]["logits_rel_err_vs_xla"] < 1e-4


def test_train_phase_cpu():
    out = chip_smoke.train_phase(SMOKE, steps=2, batch=2, seq=16)
    assert len(out["losses"]) == 2 and out["restarts"] == 0


def test_four_chip_phase_on_four_cpu_devices():
    """The four-chip path on four virtual CPU devices, in a process of its
    own (the device count is fixed when JAX starts)."""
    code = (
        "import json, sys, jax\n"
        f"sys.path.insert(0, {str(ROOT)!r})\n"
        "import chip_smoke\n"
        "from repro.configs.registry import get_smoke\n"
        "out = chip_smoke.four_chip_phase(get_smoke('qwen3-0.6b'),"
        " jax.devices(), steps=2, batch=4, seq=16)\n"
        "print(json.dumps(out))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    assert out["rel_diff"] <= chip_smoke.FOUR_CHIP_LOSS_RTOL
    per_device = out["bytes_per_device"]
    assert len(per_device) == 4 and len(set(per_device.values())) == 1


def test_main_refuses_without_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code != 0
    out = capsys.readouterr().out
    assert "phase device: FAILED" in out and _no_ok_line(out)


def test_script_alone_fails(tmp_path):
    """Copied into a directory without the rest of the repository, the
    script cannot import the system and must not report success."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert _no_ok_line(proc.stdout)
