"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Run:
    PYTHONPATH=src python -m repro.launch.dryrun [--arch A] [--shape S]
        [--multi-pod] [--out dryrun_results]

Proves the distribution config is coherent without hardware: every cell
must lower and compile against the production mesh; the compiled artifact's
memory_analysis / cost_analysis / collective schedule feed EXPERIMENTS.md
(§Dry-run, §Roofline).
"""
# The XLA device-count override MUST precede any other import that could
# initialize jax (including `from repro...`).
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse      # noqa: E402
import json          # noqa: E402

import time          # noqa: E402
from typing import Any, Dict  # noqa: E402

import jax           # noqa: E402
import numpy as np   # noqa: E402

from repro.configs import ARCH_IDS, get_config                 # noqa: E402
from repro.launch.input_specs import (                         # noqa: E402
    batch_specs,
    cache_specs,
    cell_is_applicable,
    token_spec,
)
from repro.launch.mesh import make_production_mesh            # noqa: E402
from repro.launch.sharding import (                            # noqa: E402
    act_sharding,
    batch_shardings,
    cache_shardings,
    params_shardings,
)
from repro.models import SHAPES, abstract_params               # noqa: E402
from repro.models.decode import decode_step, prefill           # noqa: E402
from repro.training.optim import AdamW                         # noqa: E402
from repro.training.train_step import (                        # noqa: E402
    TrainStepConfig,
    make_train_step,
)

from repro.launch.hlo_analysis import analyze_hlo  # noqa: E402


def microbatches_for(arch: str) -> int:
    # trade-off: more microbatches = smaller activations but one more round
    # of FSDP weight traffic per microbatch (dominant for the MoE configs);
    # dense <4B models fit comfortably at 2 (measured: gemma-2b collective
    # term 5.4s -> 1.4s going 8 -> 2)
    return {"qwen3-moe-235b-a22b": 8, "llama4-scout-17b-a16e": 8,
            "recurrentgemma-9b": 4, "seamless-m4t-medium": 4,
            "h2o-danube-3-4b": 4, "qwen2.5-3b": 4,
            "xlstm-1.3b": 8}.get(arch, 2)


def lower_cell(arch: str, shape_name: str, multi_pod: bool) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_is_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "multi_pod": multi_pod, "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    with jax.set_mesh(mesh):
        aparams = abstract_params(cfg)
        p_sh = params_shardings(aparams, mesh, cfg)
        # seq-parallel for full-sequence shapes (the batched-q-block chunked
        # attention keeps the seq dim shardable); decode has seq=1
        seq = shape.seq_len if shape.kind != "decode" else None
        sh = act_sharding(cfg, mesh, shape.global_batch, seq=seq)

        if shape.kind == "train":
            opt = AdamW()
            aopt = jax.eval_shape(opt.init, aparams)
            o_sh = jax.tree_util.tree_map(
                lambda l, ps=None: None, aopt)  # placeholder, built below
            # moments shard exactly like their parameter
            o_sh = type(aopt)(
                step=jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec()),
                # moments always fully sharded (ZeRO-1/3 both shard state)
                m=params_shardings(aparams, mesh, cfg, policy="zero3"),
                v=params_shardings(aparams, mesh, cfg, policy="zero3"),
            )
            b_spec = batch_specs(cfg, shape, with_labels=True)
            b_sh = batch_shardings(cfg, mesh, shape.global_batch, "train")
            step = make_train_step(
                cfg, opt,
                TrainStepConfig(microbatches=microbatches_for(arch)),
                sh=sh)
            lowered = jax.jit(
                step,
                in_shardings=(p_sh, o_sh, b_sh),
                out_shardings=(p_sh, o_sh, None),
            ).lower(aparams, aopt, b_spec)
        elif shape.kind == "prefill":
            b_spec = batch_specs(cfg, shape, with_labels=False)
            b_sh = batch_shardings(cfg, mesh, shape.global_batch, "prefill")
            acache = cache_specs(cfg, shape)
            c_sh = cache_shardings(acache, cfg, mesh, shape.global_batch,
                                   for_decode=False)

            def prefill_fn(params, batch):
                return prefill(params, cfg, batch, max_len=shape.seq_len,
                               sh=sh)

            lowered = jax.jit(
                prefill_fn,
                in_shardings=(p_sh, b_sh),
                out_shardings=(None, c_sh),
            ).lower(aparams, b_spec)
        else:  # decode
            acache = cache_specs(cfg, shape)
            c_sh = cache_shardings(acache, cfg, mesh, shape.global_batch)

            def serve_step(params, cache, token):
                return decode_step(params, cfg, cache, token, sh=sh)

            lowered = jax.jit(
                serve_step,
                in_shardings=(p_sh, c_sh, None),
                out_shardings=(None, c_sh),
            ).lower(aparams, acache, token_spec(shape))

        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    # loop-expanded per-device roofline inputs (see hlo_analysis.py);
    # raw cost_analysis kept for comparison (it counts while bodies once)
    expanded = analyze_hlo(hlo)
    coll = {k[len("coll_"):]: v for k, v in expanded.items()
            if k.startswith("coll_")}
    report = {
        "arch": arch,
        "shape": shape_name,
        "multi_pod": multi_pod,
        "status": "ok",
        "chips": n_chips,
        "compile_s": round(time.time() - t0, 1),
        "per_device": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "flops": expanded["flops"],
            "bytes_accessed": expanded["mem_bytes"],
            "flops_raw_costanalysis": float(cost.get("flops", 0.0)),
            "collective_bytes": coll,
        },
        "params": cfg.n_params(),
        "active_params": cfg.n_active_params(),
        "tokens": shape.tokens if shape.kind != "decode"
        else shape.global_batch,
    }
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_IDS + [None])
    ap.add_argument("--shape", default=None,
                    choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="dryrun_results")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ARCH_IDS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'pod2' if mp else 'pod1'}"
                try:
                    rep = lower_cell(arch, shape, mp)
                except Exception as e:  # a dry-run failure is a bug
                    rep = {"arch": arch, "shape": shape, "multi_pod": mp,
                           "status": "FAILED", "error": repr(e)[:500]}
                    failures += 1
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(rep, f, indent=2)
                status = rep["status"]
                extra = ""
                if status == "ok":
                    pd = rep["per_device"]
                    extra = (f" mem={(pd['argument_bytes']+pd['temp_bytes'])/2**30:.2f}GiB"
                             f" flops={pd['flops']:.3g}"
                             f" coll={pd['collective_bytes'].get('total', 0)/2**30:.3f}GiB"
                             f" compile={rep['compile_s']}s")
                print(f"[{status:>7}] {tag}{extra}", flush=True)
    if failures:
        raise SystemExit(f"{failures} dry-run cells FAILED")


if __name__ == "__main__":
    main()
