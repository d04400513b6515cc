"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); the mix's ``kind`` picks the driver in
``bench/kinds/``.  Everything runs in this one process: set-up (data made
from the seed, warm-up of this cell's shapes), the measured window of
``--seconds``, then the comparison with the plain reference that decides
``correct``.  With ``--trace 1`` the window runs under the profiler and the
cell's per-layer metrics are reported instead of its end-to-end ones.

Earlier lines on standard output say what set-up compiled, what the window
did and what was compared.  The numbers compared are the last lines on
standard error, each beside its limit.  The last line on standard output
is one JSON object.  Without a TPU (or with fewer chips than the cell
needs) the command exits non-zero and prints no result.

Two more flags never print a result line: ``--rehearse`` runs the cell at
the tiny sizes of its files' ``rehearse`` blocks on whatever JAX finds (the
CPU here), and ``--control`` computes the control (the reference in
bfloat16 in the program's place) and prints what it reads.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is counted from process start

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "src"))

from bench import common  # noqa: E402


class Context:
    """What a kind's driver gets: the cell's files, the seed, the clock,
    where to write, and the tracing switch."""

    def __init__(self, args, spec: dict):
        self.cell = common.find_cell(spec, args.workload)
        self.chips = self.cell["chips"]
        self.config = common.overrides(
            common.load_config(spec, self.cell["config"]), args.rehearse)
        for block in ("data", "svm"):
            self.config[block] = common.overrides(self.config[block],
                                                  args.rehearse)
        self.traffic = common.overrides(
            common.load_traffic(self.cell["traffic"]), args.rehearse)
        self.seed, self.seconds = args.seed, args.seconds
        self.pseed = common.program_seed(args.seed)
        self.trace = bool(args.trace)
        self.rehearse = args.rehearse
        self.t_start = T_START
        self.clock = common.CompileClock()
        self.tmp = tempfile.mkdtemp(prefix="bench-")
        self.trace_dir = os.path.join(self.tmp, "trace")

    def say(self, line: str) -> None:
        print(line, flush=True)

    @contextlib.contextmanager
    def tracing(self):
        """The profiler around the window, in ``--trace 1`` runs only."""
        if not self.trace:
            yield
            return
        import jax
        opts = jax.profiler.ProfileOptions()
        # Python frames name the host work in gaps; a traffic file turns
        # them on where the host path runs few Python calls per second
        opts.python_tracer_level = int(self.traffic.get("trace_python", 0))
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            yield
        finally:
            jax.profiler.stop_trace()

    def trace_summary(self, program: str | None) -> dict:
        from bench import trace_reduce
        paths = glob.glob(os.path.join(self.trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError("the profiler wrote no trace")
        t = common.now()
        out = trace_reduce.summarize(trace_reduce.load(paths[0]),
                                     program=program, chips=self.chips)
        self.say(f"trace {os.path.getsize(paths[0])} bytes reduced in "
                 f"{common.now() - t:.3f} s: busy {out['busy_s']:.6f} s of "
                 f"{out['window_s']:.6f} s")
        return out


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="tiny sizes on any backend; prints no result line")
    p.add_argument("--control", action="store_true",
                   help="the bfloat16 reference in the program's place; "
                        "prints no result line")
    return p.parse_args(argv)


def result_line(spec: dict, ctx: Context, record: dict) -> dict:
    """The contract's JSON object; ``checks`` comes last."""
    section = "per_layer" if ctx.trace else "end_to_end"
    metrics = common.read_metrics(
        common.cell_metrics(spec, ctx.cell["name"], section), record,
        rehearse=ctx.rehearse)
    checks = record["checks"]
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    out = {"correct": correct, "attempted": record["attempted"],
           "failed": record["failed"], "metrics": metrics,
           "device": dict(record["device"])}
    tr = record.get("trace")
    if tr:
        out["device"]["busy_s"] = tr["busy_s"]
        out["device"]["window_s"] = tr["window_s"]
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    args = parse(argv)
    spec = common.benchmark()
    cell = common.find_cell(spec, args.workload)
    if not args.rehearse:
        common.require_devices(cell["chips"])
    common.enable_compile_cache()
    ctx = Context(args, spec)
    try:
        kind = common.load_kind(ctx.traffic["kind"])
        if args.control:
            checks = kind.control(ctx)
            for name, c in checks.items():
                print(f"control {name}: {c['value']!r} (limit "
                      f"{c['limit']!r})", flush=True)
            return 0
        record = kind.run(ctx)
        if ctx.trace:
            try:
                record["trace"] = ctx.trace_summary(kind.PROGRAM)
            except ValueError as e:
                if not args.rehearse:       # the CPU has no device plane
                    raise
                print(f"rehearsal: trace not reduced ({e})", flush=True)
                record["trace"] = None
        out = result_line(spec, ctx, record)
    finally:
        shutil.rmtree(ctx.tmp, ignore_errors=True)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    if args.rehearse:
        print("rehearsal: " + json.dumps(out), flush=True)
        return 0
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
