"""``chip_smoke.py`` end to end at a tiny size on the CPU: every phase, its
parity checks and its last line, and the four-chip phase on four virtual
devices. The device check and the compiled-kernel checks only hold on a
TPU, so these tests replace those functions; the script itself has no way
around them."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import chip_smoke
from repro.configs.registry import smoke_config


@pytest.fixture
def on_cpu(monkeypatch):
    import jax

    d = jax.devices()[0]
    monkeypatch.setattr(
        chip_smoke,
        "require_tpu",
        lambda: {"platform": d.platform, "kind": d.device_kind, "count": 1},
    )
    kernels = []
    monkeypatch.setattr(
        chip_smoke, "assert_kernel_compiled", lambda name, c: kernels.append(name)
    )
    monkeypatch.setattr(
        chip_smoke, "assert_sweep_kernel", lambda call: kernels.append("_sweep")
    )
    monkeypatch.setattr(chip_smoke, "enable_compile_cache", lambda: None)
    return kernels


def test_device_check_refuses_the_cpu():
    with pytest.raises(SystemExit, match="needs a TPU"):
        chip_smoke.require_tpu()


def test_sweep_phase_tiny(on_cpu):
    # pooled over 3 seeds x 4000 requests, as test_jaxsim's 1% gate
    chip_smoke.phase_sweep(
        0, scorer_shape=(3, 2, 4000), throughput_shape=(3, 1, 4000),
        mask_shape=(8, 300),
    )
    assert on_cpu == ["_sweep", "cold_scan float32", "cold_scan float64"]


def test_serve_phase_tiny_and_last_line(on_cpu, monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "phase_sweep", lambda seed: None)
    serve, cfg = chip_smoke.phase_serve, smoke_config("qwen3-1.7b")
    monkeypatch.setattr(
        chip_smoke,
        "phase_serve",
        lambda seed: serve(seed, cfg, prompt_len=16, new_tokens=4),
    )
    chip_smoke.main([])
    out = capsys.readouterr().out.strip().splitlines()
    assert on_cpu == ["prefill (Pallas flash attention)"]
    assert json.loads(out[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    assert any("TTFT" in line for line in out)


FOUR_CHIPS = """
import chip_smoke
from repro.configs.registry import smoke_config
chip_smoke.phase_four_chips(0, smoke_config("qwen3-1.7b"), prompt_len=16, new_tokens=4)
"""


def test_four_chip_phase_tiny_on_virtual_devices():
    """The sharded decode on four virtual CPU devices, in a child process:
    the device count is fixed when JAX starts."""
    root = Path(chip_smoke.__file__).parent
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=str(root),
    )
    out = subprocess.run(
        [sys.executable, "-c", FOUR_CHIPS],
        env=env,
        cwd=root,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "parameters span 4 devices" in out.stdout
    assert "decode logits, 4-chip vs 1-chip" in out.stdout
