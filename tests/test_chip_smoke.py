"""chip_smoke.py off the chip: it refuses to run without a TPU, and each of
its phases passes at a tiny size on the CPU (the four-device phases on four
forced host devices, in a subprocess because the device count locks at JAX
start-up)."""
import importlib.util
import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_refuses_without_tpu(chip_smoke, capsys, argv):
    assert chip_smoke.main(argv) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_sssp_phase_small(chip_smoke):
    chip_smoke.phase_sssp(0, n=240, edge_p=0.1, places=8, graphs=2,
                          require_kernel=False)


def test_serve_phase_reduced(chip_smoke):
    chip_smoke.phase_serve(0, reduced=True, requests=24, prompt=8,
                           max_new=4, slots=3, max_len=32, staging_rows=24)


def test_four_device_phases_on_host_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    code = (
        "import chip_smoke as cs\n"
        "cs.phase_sharded_sssp(0, n=120, edge_p=0.15, places=4)\n"
        "cs.phase_batch_place()\n"
        "cs.phase_pod_steal()\n"
        "print('FOUR_DEVICE_OK')\n"
    )
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert "FOUR_DEVICE_OK" in out.stdout, (out.stdout[-1000:],
                                            out.stderr[-2000:])
    assert "BATCH_PLACE_OK B=2 P=2" in out.stdout
    assert "POD_STEAL_OK pods=2" in out.stdout
