"""The harness finds every cell's files by name, and refuses what it
cannot run."""
import json
import subprocess
import sys

import pytest

from bench import spec

MANIFEST = spec.manifest()


@pytest.mark.parametrize("workload", [w["name"] for w in MANIFEST["workloads"]])
def test_every_cell_finds_its_files(workload):
    cell = spec.cell(workload)
    assert cell.config["name"] == next(
        w["config"] for w in MANIFEST["workloads"] if w["name"] == workload)
    assert cell.traffic["load"]
    assert cell.limits["limits"]
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(spec.metric_reader(m["name"]))


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_config_files_hold_what_the_manifest_says(config):
    with open(spec.ROOT / config["file"]) as f:
        body = json.load(f)
    assert body["name"] == config["name"]
    assert sorted(body["reduced_from_source"]) == sorted(config["reduced"])


def test_unknown_workload_is_refused():
    with pytest.raises(spec.SpecError, match="unknown workload"):
        spec.cell("no_such.cell")


def test_unknown_metric_reader_is_refused():
    with pytest.raises(spec.SpecError, match="no reader"):
        spec.metric_reader("no_such_metric")


def test_peaks_known_and_unknown_device_kind():
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError, match="not in bench/peaks.json"):
        spec.peaks("cpu")


def test_a_host_without_a_tpu_gets_no_result():
    proc = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         MANIFEST["workloads"][0]["name"], "--seed", "2147483653",
         "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr
