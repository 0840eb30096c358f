"""Find the shortest execution period an open-loop cell's engine sustains.

    python3 bench/sweep.py --workload bad51.alerts --seed <n> --seconds <s> \
        --periods 0.5 0.3 0.2 ...

One engine is built and preloaded once; then, for each period, the cell's
open loop runs at its rate for ``--seconds`` (after its own warm-up), and
the line printed says whether the executions kept to the schedule: the
lag of each execution (materialisation minus due time) must not grow from
the first quarter of the window to the last by a tenth of a period or
more, and no execution may start a tenth of a period late or more. The
cell's traffic file then fixes its period at 1.25 times the shortest
sustained one. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def sweep(c, seed: int, seconds: float, periods) -> list:
    import numpy as np

    from bench import deploy, generator, loops
    cfg = c.cfg
    eng, _ = deploy.build(cfg, generator.make(cfg, 1, 1, seed))
    out = []
    for i, period in enumerate(periods):
        traffic = dict(c.traffic, period_s=period)
        _, n, n_batches = loops.plan(cfg, traffic, seconds)
        data = generator.make(cfg, n, n_batches, seed + 1 + i)
        drv = loops.OpenLoop(eng, cfg, traffic, data, seed)
        drv.warm()
        drv.window(seconds)
        drv.finish()
        lag = np.array([e.done - e.due for e in drv.execs])
        q = max(1, lag.size // 4)
        growth = float(lag[-q:].mean() - lag[:q].mean())
        late = float(max(drv.lateness))
        row = {"period_s": period, "records": n, "executions": lag.size,
               "lag_first_ms": float(lag[:q].mean() * 1e3),
               "lag_last_ms": float(lag[-q:].mean() * 1e3),
               "lag_max_ms": float(lag.max() * 1e3),
               "lateness_max_ms": late * 1e3,
               "sustained": growth < period / 10 and late < period / 10}
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def main(argv=None) -> int:
    from bench import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--periods", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    c = run.cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU; refusing to run", file=sys.stderr)
        return 2
    run.enable_cache()
    sweep(c, args.seed, args.seconds, args.periods)
    return 0


if __name__ == "__main__":
    sys.exit(main())
