"""Distribution machinery on a small forced-device mesh (CI-scale dry run).

Runs in a subprocess because XLA_FLAGS must be set before jax initializes
(the main test process already owns a single-device backend).
"""
import json
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

from repro.configs import get_smoke_config
from repro.launch.sharding import (act_sharding, batch_shardings,
                                   cache_shardings, params_shardings)
from repro.launch.input_specs import cache_specs, token_spec
from repro.models import SHAPES, abstract_params, init_params
from repro.models.decode import decode_step, init_cache
from repro.training.optim import AdamW
from repro.training.train_step import TrainStepConfig, make_train_step

mesh = jax.make_mesh((2, 4), ("data", "model"))
results = {}
for arch in ["qwen2.5-3b", "qwen3-moe-235b-a22b"]:
    cfg = get_smoke_config(arch)
    with jax.set_mesh(mesh):
        params = init_params(cfg, jax.random.PRNGKey(0))
        p_sh = params_shardings(jax.eval_shape(lambda: params), mesh, cfg)
        params = jax.tree_util.tree_map(jax.device_put, params, p_sh)
        opt = AdamW(lr=1e-3)
        opt_state = opt.init(params)
        sh = act_sharding(cfg, mesh, batch=8, seq=16)
        step = jax.jit(make_train_step(
            cfg, opt, TrainStepConfig(microbatches=2), sh=sh))
        rng = np.random.default_rng(0)
        batch = {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16),
                                               dtype=np.int64).astype(np.int32)),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16),
                                               dtype=np.int64).astype(np.int32)),
        }
        losses = []
        for _ in range(3):
            params, opt_state, m = step(params, opt_state, batch)
            losses.append(float(m["loss"]))
        results[arch] = losses
print("RESULT " + repr(results))
"""


def test_small_mesh_train_runs_and_learns():
    out = subprocess.run([sys.executable, "-c", SCRIPT], cwd=".",
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][-1]
    results = eval(line[len("RESULT "):])  # noqa: S307 - our own output
    for arch, losses in results.items():
        assert all(np.isfinite(l) for l in losses), (arch, losses)
        assert losses[-1] < losses[0] + 1.0, (arch, losses)


import numpy as np  # noqa: E402
