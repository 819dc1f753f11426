"""chip_smoke.py's phases at the smoke preset on the CPU.

The script drives internlm2-1.8b at published widths on a TPU; here the
same phase functions run the registered smoke config, with the Pallas
kernels interpreted, so a broken argument, path or comparison shows up
before any chip time is spent.  ``main()`` itself refuses a CPU.
"""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod           # dataclasses look it up
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def preset(cs):
    return cs.smoke_preset()


def test_phase_dense(cs, preset, capsys):
    res = cs.phase_dense(preset)
    assert res["requests"] == preset.n_requests
    assert res["tokens"] == preset.n_requests * preset.gen
    assert res["logits"]["rel_err"] <= cs.LOGIT_TOL
    assert "phase A dense/slot" in capsys.readouterr().out


def test_phase_sparse_runs_the_paged_kernel(cs, preset, monkeypatch):
    # off the TPU the engine serves paged attention through the jnp
    # reference; force the Pallas kernel (interpreted) as the chip runs it
    monkeypatch.setenv("REPRO_PAGED_BACKEND", "pallas")
    res = cs.phase_sparse(preset, expect_kernel=False)
    assert res["kernel_compiled"] is False        # interpreted on the CPU
    assert 0 < res["compressed_bytes"] < res["dense_bytes"]
    assert res["logits"]["rel_err"] <= cs.LOGIT_TOL
    # decode steps are compared too, and the planted fault is caught
    assert res["logits"]["decode_rows"] > 0
    assert res["control"]["rel_err"] > cs.LOGIT_TOL
    # the CPU serves every projection on the portable path
    assert res["sparse_projections"]["kernel"] == 0
    assert res["sparse_projections"]["portable"] > 0


def test_phase_kernels(cs, preset):
    res = cs.phase_kernels(preset, expect_kernel=False)
    assert len(res["cases"]) == 4 * len(preset.kernel_batches)
    assert res["worst_rel_err"] <= cs.KERNEL_TOL


def test_comparison_rejects_wrong_logits(cs):
    want = jax.numpy.ones((2, 8))
    with pytest.raises(cs.SmokeFailure):
        cs._compare(want * 1.5, want, cs.LOGIT_TOL, "scaled")


def test_main_refuses_a_cpu(cs, capsys, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cs.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_phase_four_chip_on_virtual_devices():
    """Phase D (1x4 mesh, four one-device replicas) on four forced CPU
    devices, in a child process so the device count can be set before
    JAX starts."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import json, chip_smoke as cs; "
            "r = cs.phase_four_chip(cs.smoke_preset()); "
            "print(json.dumps([r['mesh']['decode_rows'], "
            "r['replicas']['decode_rows'], r['replica_devices']]))")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert any(line.startswith("phase D mesh 1x4") for line in lines)
    mesh_decode, fleet_decode, devices = json.loads(lines[-1])
    assert mesh_decode > 0 and fleet_decode > 0      # decode steps compared
    assert devices == [[0], [1], [2], [3]]
