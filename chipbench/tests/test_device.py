"""The run names its device, refuses what is not a TPU, and takes its peaks
from peaks.json by device kind."""
import shutil
import subprocess
import sys

import pytest

import harness

RUN = harness.HERE / "run.py"


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        harness.peaks("TPU v99")
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_non_tpu_backend_is_refused():
    with pytest.raises(harness.NoDevice, match="not a TPU"):
        harness.require_devices(1)


@pytest.mark.parametrize("workload", ["hymba-serve-decode", "mamba2-train"])
def test_run_without_tpu_prints_no_result(workload):
    p = subprocess.run([sys.executable, str(RUN), "--workload", workload,
                        "--seed", "3000000000", "--seconds", "1"],
                       capture_output=True, text=True, timeout=300,
                       cwd=harness.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_run_outside_a_checkout_prints_no_result(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "mamba2-train", "--seed", "1", "--seconds", "1"],
                       capture_output=True, text=True, timeout=300, cwd=tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_every_cell_finds_its_files():
    bench = harness.read_json(harness.ROOT / "BENCHMARK.json")
    for w in bench["workloads"]:
        cell = harness.cell(w["name"], bench)
        assert cell.limits and cell.traffic["kind"] in ("serve", "train")
        assert hasattr(cell.cmod, "init_params")
        for m in cell.per_layer:
            assert (harness.HERE / "metrics" / f"{m['name']}.py").is_file()
