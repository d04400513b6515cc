"""Find the knee of a serve cell once: the highest arrival rate at which
the queue keeps up.

    python bench/sweep_rate.py --workload mnist-ovr-serve --seed 1 \\
        --seconds 8 --rates 500,1000,2000,4000

One process builds the cell's bank and queue once (as ``run.py`` does),
then offers each rate in turn as an open loop of ``--seconds`` and prints a
row per rate: requests, rows/s offered and completed, p50 / p95 / p99
latency from the due time, and the generator's lateness.  Where p95 grows
with the window instead of settling, the queue is falling behind: the knee
lies below that rate.  The cell's fixed rate is then set in its traffic
file by hand, at about four fifths of the knee; nothing reads this output.
"""
from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

from bench import common  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=8.0)
    p.add_argument("--rates", required=True,
                   help="comma-separated requests per second")
    args = p.parse_args(argv)
    spec = common.benchmark()
    cell = common.find_cell(spec, args.workload)
    common.require_devices(cell["chips"])
    common.enable_compile_cache()

    import jax

    from bench.kinds import open_loop
    from repro.core.bsgd import SVMState
    from repro.core.predict import AsyncBatchQueue, export_model

    conf = common.load_config(spec, cell["config"])
    traffic = common.load_traffic(cell["traffic"])
    data, svm = conf["data"], conf["svm"]
    sv, alpha, count, pool = open_loop.make_bank(
        data, svm, traffic, jax.random.PRNGKey(common.program_seed(
            args.seed)))
    zero = jax.numpy.zeros((data["n_classes"],), jax.numpy.int32)
    model = export_model(SVMState(sv_x=sv, alpha=alpha, count=count,
                                  step=zero, n_inserts=zero, n_merges=zero),
                         svm["gamma"])
    pool = np.asarray(pool, np.float32)
    print("rate_rps requests offered_rows_s done_rows_s p50_ms p95_ms "
          "p99_ms late_p50_ms late_p99_ms failed", flush=True)
    with AsyncBatchQueue(model, max_batch=traffic["max_batch"]) as q:
        q.warmup()
        for rate in (float(r) for r in args.rates.split(",")):
            t = dict(traffic, rate_rps=rate)
            sizes, due = open_loop.schedule(t, args.seconds, args.seed)
            t0 = common.now()
            lat, _, _, late = open_loop.open_loop(q, pool, sizes, due, t0,
                                                  args.seconds + 60)
            span = common.now() - t0
            ok = np.isfinite(lat)
            q50, q95, q99 = (np.quantile(np.where(ok, lat, np.inf), q) * 1e3
                             for q in (0.5, 0.95, 0.99))
            print(f"{rate:g} {len(sizes)} {sizes.sum() / due[-1]:.6g} "
                  f"{sizes.sum() / span:.6g} {q50:.6g} {q95:.6g} {q99:.6g} "
                  f"{np.quantile(late, 0.5) * 1e3:.6g} "
                  f"{np.quantile(late, 0.99) * 1e3:.6g} {int((~ok).sum())}",
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
