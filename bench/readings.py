"""Read the numbers that ``correct`` compares, over many seeds in one
process: the lower readings (sound runs of the program) and the upper ones
(the control), from which each limit in a traffic file is set.

    python bench/readings.py --workload mnist-ovr-train --seconds 1 \\
        --seeds 11,12,13 --control-seeds 21,22,23

Each seed runs the cell as ``run.py`` does (set-up, a short window at the
cell's own load, the comparison); each control seed runs the reference in
bfloat16 in the program's place.  Prints one line per seed and number;
the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import common, run  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, default=1.0)
    p.add_argument("--seeds", default="")
    p.add_argument("--control-seeds", default="")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    spec = common.benchmark()
    cell = common.find_cell(spec, args.workload)
    if not args.rehearse:
        common.require_devices(cell["chips"])
    common.enable_compile_cache()
    jobs = ([(int(s), False) for s in args.seeds.split(",") if s]
            + [(int(s), True) for s in args.control_seeds.split(",") if s])
    for seed, is_control in jobs:
        ctx = run.Context(run.parse([
            "--workload", args.workload, "--seed", str(seed), "--seconds",
            str(args.seconds)] + (["--rehearse"] if args.rehearse else [])),
            spec)
        ctx.t_start = common.now()
        try:
            kind = common.load_kind(ctx.traffic["kind"])
            checks = (kind.control(ctx) if is_control
                      else kind.run(ctx)["checks"])
        finally:
            shutil.rmtree(ctx.tmp, ignore_errors=True)
        for name, c in checks.items():
            print(f"READING {'control' if is_control else 'program'} "
                  f"seed {seed} {name} {c['value']!r} limit {c['limit']!r}",
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
