"""The comparison that decides ``correct`` fails a broken round: each
fault a cell can have, planted under the timed path, and the control
(the program with bfloat16 parameters), driven through a whole run at a
size the CPU can hold and held to the chip cell's limits."""

import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench_tiny import DENSE, REPO, cell, traffic

from chipbench import faults, harness, peaks


def _run(c, devices):
    return harness.run_cell(c, 2 ** 31 + 11, 0.3, False, devices, time.time(),
                            peaks.PEAKS["TPU v5 lite"], log=lambda m: None)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "loss_altered"])
def test_one_chip_cell_fails_a_planted_fault(fault):
    c = cell(DENSE, traffic(), "internlm2-local")
    with faults.planted(fault):
        assert _run(c, jax.devices()[:1])["correct"] is False


def test_one_chip_cell_fails_the_bfloat16_control(monkeypatch):
    real = harness.SystemUnderTest.__init__

    def bf16(self, cell, seed, devices, param_dtype=jnp.float32):
        real(self, cell, seed, devices, param_dtype=jnp.bfloat16)

    monkeypatch.setattr(harness.SystemUnderTest, "__init__", bf16)
    c = cell(DENSE, traffic(), "internlm2-local")
    assert _run(c, jax.devices()[:1])["correct"] is False


RING = """
import sys, time
import jax, jax.numpy as jnp
sys.path[:0] = [{tests!r}, {repo!r}, {src!r}]
from chipbench_tiny import DENSE, cell, traffic
from chipbench import faults, harness, peaks
c = cell(DENSE, traffic(silos=4), "internlm2-ring4")
run = lambda: harness.run_cell(c, 2 ** 31 + 13, 0.3, False, jax.devices()[:4], time.time(),
                               peaks.PEAKS["TPU v5 lite"], log=lambda m: None)["correct"]
print("sound", run())
for f in ("no_exchange", "unchanged", "half_batch", "loss_altered"):
    with faults.planted(f):
        print(f, run())
real = harness.SystemUnderTest.__init__
harness.SystemUnderTest.__init__ = lambda self, *a, **k: real(self, *a, param_dtype=jnp.bfloat16)
print("control_bf16", run())
"""


def test_four_silo_ring_fails_each_fault_and_the_control():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    code = RING.format(tests=str(REPO / "tests" / "chipbench"), repo=str(REPO),
                       src=str(REPO / "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    got = dict(line.split() for line in r.stdout.strip().splitlines())
    assert got == {"sound": "True", "no_exchange": "False", "unchanged": "False",
                   "half_batch": "False", "loss_altered": "False",
                   "control_bf16": "False"}
