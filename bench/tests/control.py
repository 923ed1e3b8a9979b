"""The control of the correctness check: the reference computed with
its byte and rate arithmetic in bfloat16, the precision below the
configuration's float32, put in the program's place and compared with
the float32 reference as the program is.

    python3 bench/tests/control.py <cell> [<member seed> ...]

prints, for each member seed (by default the cell's own set), the
reading of each compared number, at the cell's own size.
"""
import importlib.util
import json
import os
import sys

import ml_dtypes

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import compare, member  # noqa: E402


def control_gaps(config, traffic, seed):
    """The control's reading of each compared number for the member with
    ``seed``."""
    f = member.fabric_of(config)
    low = ml_dtypes.bfloat16
    return compare.gaps(member.simulate(config, traffic, seed, f, dtype=low),
                        member.simulate(config, traffic, seed, f))


def main(argv):
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    _, config, traffic, _, _ = run.cell_spec(argv[0])
    for seed in map(int, argv[1:] or traffic["member_seeds"]):
        print(json.dumps(dict(cell=argv[0], seed=seed,
                              control=control_gaps(config, traffic, seed))),
              flush=True)

if __name__ == "__main__":
    main(sys.argv[1:])
