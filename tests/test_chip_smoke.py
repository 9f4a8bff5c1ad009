"""``chip_smoke.py`` on the CPU: each phase at a tiny size (Pallas in
interpret mode), the refusal to run without a TPU, the four-silo phase
on four virtual CPU devices, and where the compile cache lives."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402
from repro.launch import compile_cache  # noqa: E402

TINY_TRAIN = ["--arch", "internlm2-1.8b", "--reduced", "--seq-len", "64",
              "--batch-per-silo", "4", "--steps", "6", "--lr", "0.1"]
TINY_VOCAB = 512  # ModelConfig.reduced() caps the vocabulary here


def test_designer_phase_tiny():
    chip_smoke.phase_designer(("gaia",), n=24, batch=3, degree=4)


def test_kernels_phase_tiny():
    chip_smoke.phase_kernels(attn=(1, 128, 2, 2, 128), mlstm=(1, 128, 2, 128),
                             gossip_n=4096, seg=(3, 200, 130), interpret=True)


def test_trainer_phase_tiny(monkeypatch, tmp_path):
    # keep the trainer's process-wide settings out of this test process:
    # its CPU device count and its compile cache
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    report = chip_smoke.phase_trainer(TINY_TRAIN + ["--silos", "1"],
                                      vocab_size=TINY_VOCAB)
    assert len(report["losses"]) == 6


@pytest.mark.parametrize("argv", [[], ["--four-chip"]])
def test_main_refuses_the_cpu(argv, capsys):
    assert chip_smoke.main(argv) != 0
    captured = capsys.readouterr()
    assert "'cpu'" in captured.err and '"ok"' not in captured.out


def test_four_chip_phase_on_virtual_devices(tmp_path):
    """Each silo's state on its own device and the ppermute gossip round
    equal to the einsum one, on four virtual CPU devices (a subprocess:
    this process has one device)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=str(REPO / "src"))
    code = ("import chip_smoke; chip_smoke.phase_four_chip("
            f"{TINY_TRAIN + ['--silos', '4', '--topology', 'ring', '--gossip-impl', 'ppermute']!r}"
            f", vocab_size={TINY_VOCAB})")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-3000:]
    assert "each silo's state on its own device (4 devices): True" in out
    assert "shard_map vs einsum" in out and ": ok" in out


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_dir(env_dir, monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.enable_compile_cache()
        if env_dir:
            # JAX reads the variable itself; nothing is set in code
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert got == str(REPO / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
