"""The control: the reference in the program's place, at lower precision.

    python3 bench/control.py --workload <name> --seeds <n> [<n> ...]

For each seed it generates the cell's tables at full size, has the cell's
operation compute the answers the reference gives with vertex ids carried
in int16, the nearest precision below the configuration's int32
(``control_answers``), and feeds them through the same comparison that
judges the program's answers (``compare``).  It prints every compared
number beside its limit; one of them has to come out above its limit:
that is what shows the check can fail.  The benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, Tuple

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import data, spec  # noqa: E402
from bench.harness import Run  # noqa: E402

LOWER = np.int16


def control_checks(workload: str, seed: int, shrink: int = 1,
                   count: int = 3) -> Dict[str, Tuple[float, float]]:
    cell = spec.resolve(spec.load_benchmark(), workload)
    config, traffic = cell["config"], cell["traffic"]
    run = Run(config=config, traffic=traffic,
              tables=data.make_tables(config, seed, shrink), db=None,
              model=None, rng=np.random.default_rng(seed))
    operation = spec.operation(traffic["operation"])
    answers = operation.control_answers(run, traffic, LOWER, count)
    return operation.compare(run, traffic, answers)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    for seed in args.seeds:
        checks = control_checks(args.workload, seed)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "fails": any(v > lim for v, lim in checks.values()),
            "control": {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
