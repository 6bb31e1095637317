"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
phases run end to end at tiny shapes on the CPU, with the kernels in
interpret mode, so the script cannot rot between chip runs."""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import pytest

from conftest import REPO, load_chip_smoke

SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    return load_chip_smoke()


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, SCRIPT], capture_output=True, text=True,
                       env=env, timeout=300, cwd=REPO)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout


def test_kernel_phases_at_tiny_shapes(smoke):
    smoke.check_flash([
        ("f32", (1, 256, 256, 4, 2, 64), jnp.float32, True, 0, 128),
        ("decode", (2, 1, 256, 2, 1, 128), jnp.float32, False, 0, 1),
        ("bf16 window", (1, 256, 256, 4, 2, 80), jnp.bfloat16, True, 128, 128),
    ], seed=0, interpret=True)
    smoke.check_block_quant([("narrow", (64, 512)), ("wide", (40, 6912))],
                            seed=0, interpret=True)
    smoke.check_mamba([("tiny", (1, 256, 512, 16))], seed=0, interpret=True)


def test_serve_phase_reduced(smoke):
    smoke.check_serve(smoke.SERVE_ARCH, reduced=True, batch=4, prompt_len=16,
                      gen_tokens=4, seed=0)


def test_collectives_phase_on_four_fake_devices():
    prog = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import importlib.util, jax
        spec = importlib.util.spec_from_file_location("chip_smoke", {SCRIPT!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)  # a fresh process: conftest is not on its path
        smoke.check_collectives(jax.devices()[:4], (64, 6912), seed=0)
        print("collectives ok")
        """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True, text=True,
                       env=env, timeout=420, cwd=REPO)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    assert "collectives ok" in r.stdout
