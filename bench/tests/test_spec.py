"""``BENCHMARK.json`` against the contract's shape, every name resolving
to its files, and a new cell added by files and entries alone."""
import json
import os
import re
import shutil
import subprocess
import sys

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_every_cell_resolves_to_its_files():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.resolve(bench, w["name"])
        assert cell["config"]["name"] == w["config"]
        operation = spec.operation(cell["traffic"]["operation"])
        assert callable(operation.compare)
        assert callable(operation.control_answers)
        for metric in cell["traffic"]["end_to_end"]:
            assert metric in {m["name"] for m in cell["end_to_end"]}
        names = [m["name"] for m in cell["end_to_end"]]
        assert "setup_s" in names and len(names) >= 2
        assert cell["per_layer"]
        moved = {m["moves"] for m in cell["per_layer"]}
        assert moved <= set(names), (w["name"], moved, names)
        for m in cell["per_layer"]:
            assert callable(spec.metric_reader(m["name"]).read)


def test_benchmark_keys_names_and_bounds():
    bench = spec.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(spec.ROOT, c["file"]))
        assert c["file"].startswith("bench/")
    assert all(w["chips"] == 1 for w in bench["workloads"])
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) < 64 * 1024


def test_peaks_table_refuses_an_unknown_device():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    try:
        spec.peaks("TPU v9 imaginary")
    except KeyError:
        return
    raise AssertionError("an unknown device kind must be an error")


def test_readers_of_one_quantity_share_a_file():
    assert spec.metric_reader("device_idle.extract").read is not None
    assert spec.metric_reader("device_idle.analyze").__file__.endswith(
        os.path.join("metrics", "device_idle.py"))
    assert spec.metric_reader("bloom_probe_roofline").__file__.endswith(
        "bloom_probe_roofline.py")


NEW_OPERATION = """
import time

from bench import drive, reference
from bench.operations import extract


class Operation(extract.Operation):
    # the extract, timed by its own percentile, plus a count of its own
    def end_to_end(self, ops):
        lat = drive.latencies(ops)
        return {"extract_p95_ms": 1e3 * drive.statistic("p95", lat),
                "edges_per_s": self.edges * len(ops) / lat.sum()}

    def setup(self):
        super().setup()
        self.edges = sum(int(t.valid.sum()) for t in
                         self.engine.extract(self.run.model).graph.edges
                         .values())


compare = extract.compare
control_answers = extract.control_answers
"""


def test_a_cell_is_added_by_new_files_and_entries_only(tmp_path):
    """Copy the benchmark; add a traffic file, an operation, a metric
    reader and their entries, with a new percentile end-to-end metric; run
    the new cell through the unchanged harness."""
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = spec.load_benchmark()
    (tmp_path / "bench" / "traffic" / "extract_tail.json").write_text(
        json.dumps({"operation": "extract_tail"}))
    (tmp_path / "bench" / "operations" / "extract_tail.py").write_text(
        NEW_OPERATION)
    (tmp_path / "bench" / "metrics" / "ops.py").write_text(
        "def read(run):\n    return len(run.ops)\n")
    cell = "tpcds_sf1_fraud.extract_tail"
    bench["workloads"].append({
        "name": cell, "config": "tpcds_sf1_fraud",
        "traffic": "extract_tail", "chips": 1, "why": "test"})
    bench["end_to_end"] += [
        {"name": "extract_p95_ms", "unit": "ms", "better": "lower",
         "bound": 0.05, "source": "host_clock", "workloads": [cell]},
        {"name": "edges_per_s", "unit": "edges/s", "better": "higher",
         "bound": 0.05, "source": "host_clock", "workloads": [cell]}]
    bench["per_layer"].append({
        "name": "ops.extract_tail", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "pipeline",
        "moves": "extract_p95_ms", "workloads": [cell]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    code = (
        "import json, sys, time\n"
        "from bench import harness\n"
        "for trace in (False, True):\n"
        f"    r = harness.run_cell({cell!r}, 7, 0.2, trace, "
        "t_start=time.perf_counter(), require_tpu=False, shrink=2000)\n"
        "    print(json.dumps(r))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(tmp_path),
                                           os.path.join(spec.ROOT, "src")]))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=tmp_path)
    assert out.returncode == 0, out.stderr[-3000:]
    plain, traced = (json.loads(line) for line in
                     out.stdout.strip().splitlines()[-2:])
    assert plain["correct"] and traced["correct"]
    assert set(plain["metrics"]) == {"setup_s", "extract_p95_ms",
                                     "edges_per_s"}
    assert plain["metrics"]["extract_p95_ms"]["value"] > 0
    assert plain["metrics"]["edges_per_s"]["value"] > 0
    assert traced["metrics"]["ops.extract_tail"]["value"] == \
        traced["attempted"]
