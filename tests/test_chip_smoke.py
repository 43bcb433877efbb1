"""``chip_smoke.py``: its work at the ``quick`` size on the CPU, and its
refusal to run anywhere but on a TPU."""
from __future__ import annotations

import importlib.util
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("dataset, shape", [
    ("e3sm", (36, 5, 1536)),                      # E3SM geometry, k=5
    ("s3d", (144, 10, 4640)),                     # S3D geometry, k=10
])
def test_single_chip_work_at_quick_size(chip_smoke, tmp_path, dataset,
                                        shape):
    tau = chip_smoke.TAUS[dataset]
    facts = chip_smoke.run_single_chip(dataset, quick=True, tau=tau,
                                       out_dir=tmp_path)
    assert facts["shape"] == shape
    assert facts["max_l2"] <= tau * (1 + 1e-5)
    assert 0 < facts["coded_share"] <= 1 and facts["ratio"] > 1
    assert not list(tmp_path.iterdir())           # the .rba is cleaned up


@pytest.mark.parametrize("argv", [[], ["--mesh", "4"], ["--dataset", "s3d"]])
def test_main_refuses_a_backend_without_tpu(chip_smoke, capsys, argv):
    assert chip_smoke.main(argv) != 0
    out, err = capsys.readouterr()
    assert '"ok"' not in out and "TPU" in err


def test_mesh_work_on_four_virtual_devices():
    # the device count freezes at the first jax import: own process
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import chip_smoke; "
            "print(chip_smoke.run_mesh(4, quick=True, chunk_hyperblocks=4))")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "'byte_identical': True" in proc.stdout
