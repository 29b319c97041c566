"""A short run of every cell through the harness on the CPU at a tiny
size: a well-formed result that is correct, with no device metric; the
command itself refuses a host without a TPU."""
import json
import os
import subprocess
import sys
import time

import pytest

from bench import harness, spec

SHRINK = 2000
CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


def run(cell, trace=False, seed=2**31 + 3):
    return harness.run_cell(cell, seed, 0.3, trace,
                            t_start=time.perf_counter(),
                            require_tpu=False, shrink=SHRINK)


def _well_formed(result, cell_spec, trace):
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "checks"
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert "busy_s" not in result["device"]
    wanted = cell_spec["per_layer"] if trace else cell_spec["end_to_end"]
    names = {m["name"] for m in wanted}
    assert set(result["metrics"]) <= names
    for name, m in result["metrics"].items():
        assert isinstance(m["value"], (int, float)) and m["unit"]
        assert "roofline" not in name and "idle" not in name
    json.dumps(result)
    for c in result["checks"].values():
        assert set(c) == {"value", "limit"}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal(cell):
    cell_spec = spec.resolve(spec.load_benchmark(), cell)
    result = run(cell)
    _well_formed(result, cell_spec, trace=False)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {m["name"]
                                      for m in cell_spec["end_to_end"]}
    assert all(m["value"] > 0 for m in result["metrics"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reads_host_metrics(cell):
    cell_spec = spec.resolve(spec.load_benchmark(), cell)
    result = run(cell, trace=True)
    _well_formed(result, cell_spec, trace=True)
    assert result["correct"]
    host_only = {m["name"] for m in cell_spec["per_layer"]
                 if m["source"] != "device_trace"}
    assert host_only <= set(result["metrics"])


def test_command_refuses_a_host_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "NoAccelerator" in out.stderr
