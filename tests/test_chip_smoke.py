"""chip_smoke.py: its phases at a tiny size on CPU, and its refusal to run
without a TPU."""
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

from repro.runtime import platform  # noqa: E402


def test_phases_run_at_tiny_size():
    """Fit, route, quality floors, kernel parity (interpret mode here) and
    serving, with every assertion of the chip run."""
    out = chip_smoke.run_one_chip(n=2000, d=32, n_query=64,
                                  samples_per_node=2000, topk_rows=256,
                                  acc_floor=0.8)
    assert out["recall"] >= chip_smoke.RECALL_FLOOR
    assert out["accuracy"] >= 0.8


def test_four_chip_phase_on_four_cpu_devices():
    """The --chips 4 phase on four virtual CPU devices (its own process:
    the device count is fixed when JAX starts): the distributed fit, its
    placement, weight and accuracy checks."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import chip_smoke; "
            "print('FOUR_OK', chip_smoke.run_four_chips(n=3000, d=32))")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "FOUR_OK" in proc.stdout
    assert "weights sharded vs one device: bitwise=True" in proc.stdout


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_without_tpu(argv, capsys):
    assert jax.default_backend() != "tpu"
    assert chip_smoke.main(argv) != 0
    captured = capsys.readouterr()
    assert captured.out == ""             # no phase ran, no result line
    assert "no TPU" in captured.err


def test_compile_cache_placement(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX; otherwise the
    cache goes to the fixed <repo>/.jax_cache."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, val: calls.append((name, val)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert platform.use_compile_cache() == "/elsewhere/cache"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(REPO, ".jax_cache")
    assert platform.use_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)]
