"""Distributed (hash-partitioned shard_map) join == single-device oracle.

Subprocess with forced host devices (main process owns a 1-device backend).
"""
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow  # compile-heavy; CI runs -m "not slow"

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax
import jax.numpy as jnp
import numpy as np

from repro.relational import Table, sort_merge_join
from repro.relational.distributed import distributed_join

mesh = jax.make_mesh((8,), ("data",))
rng = np.random.default_rng(0)
NL, NR = 512, 768
left = Table.from_arrays(
    k=rng.integers(0, 64, NL).astype(np.int32),
    a=np.arange(NL, dtype=np.int32)).prefix("L")
right = Table.from_arrays(
    k=rng.integers(0, 64, NR).astype(np.int32),
    b=np.arange(NR, dtype=np.int32)).prefix("R")

oracle = sort_merge_join(left, right, on=[("L.k", "R.k")])
with jax.set_mesh(mesh):
    got = distributed_join(left, right, on=[("L.k", "R.k")], mesh=mesh,
                           capacity_per_shard=1 << 13)
want = oracle.to_rowset(["L.a", "R.b"])
have = got.to_rowset(["L.a", "R.b"])
assert have == want, (len(have), len(want))
print("RESULT ok", len(want))
"""


def test_distributed_join_matches_oracle():
    out = subprocess.run([sys.executable, "-c", SCRIPT], cwd=".",
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "RESULT ok" in out.stdout
