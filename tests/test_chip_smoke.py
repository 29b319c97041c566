"""``chip_smoke.py`` end to end at a tiny size on the CPU.

The script is the proof that the main path runs on the chip; this
rehearsal keeps its phases and checks from rotting between chip runs.  It
forces the Pallas kernels (interpret mode) and skips only the checks that
need a chip.  A subprocess keeps its engines, metrics and compile caches
out of this test process.
"""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = """
import json, os, chip_smoke
device = chip_smoke.main(tpcds_sf=2, dblp_scale=1, use_kernel=True,
                         require_tpu=False)
print(json.dumps({"ok": True, "device": device}), flush=True)
os._exit(0)
"""


def test_smoke_rehearsal_on_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", SCRIPT], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": 1}}
    for phase in ("a: tpcds fraud extract", "a: tpcds fraud analyze",
                  "b: dblp extract", "c: served refresh"):
        assert f"phase {phase}: wall_s=" in out.stdout
    assert "refresh published on the delta path" in out.stdout


def test_smoke_refuses_a_host_without_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr
