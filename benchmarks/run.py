# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark driver: python -m benchmarks.run [--only fig16,table1,...]
                                              [--smoke] [--json PATH]

CPU-scaled versions of every paper experiment (structure preserved, counts
shrunk — see benchmarks/common.py). The paper's *ratios* are the validation
target; each derived column quotes the paper's number where applicable.

``--smoke`` shrinks every suite ~16x (CI-sized; ratios stay meaningful,
absolute times do not). ``--json PATH`` additionally dumps every emitted row
as JSON — CI uploads ``BENCH_smoke.json`` as the perf-trajectory artifact.
See benchmarks/README.md for the full catalogue.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from benchmarks import (aggregation, bad_index, broker_ops, churn, common,
                        compact_join, enrich, group_size, kernel_perf,
                        max_subscriptions, multi_channel, pipeline,
                        query_plan, real_world, scaling, sharded)
from repro.compile_cache import enable_compile_cache

SUITES = {
    "fig12_13_group_size": group_size.run,
    "table1_aggregation": aggregation.run,
    "table2_broker_ops": broker_ops.run,
    "fig14_query_plan": query_plan.run,
    "fig16_bad_index": bad_index.run,
    "fig17_max_subscriptions": max_subscriptions.run,
    "fig18_19_scaling": scaling.run,
    "fig21_real_world": real_world.run,
    "kernel_perf": kernel_perf.run,
    "multi_channel": multi_channel.run,
    "churn_sustained": churn.run,
    "compact_join": compact_join.run,
    "sharded_scaling": sharded.run,
    "pipeline_overlap": pipeline.run,
    "enrich_ranked": enrich.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite substrings")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI (see common.scale)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also dump emitted rows as JSON (e.g. BENCH_smoke.json)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        common.set_smoke()
    print("name,us_per_call,derived")
    t0 = time.time()
    for name, fn in SUITES.items():
        if args.only and not any(s in name for s in args.only.split(",")):
            continue
        print(f"# --- {name} ---", flush=True)
        fn(np.random.default_rng(0))
    total = time.time() - t0
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"smoke": common.SMOKE, "total_s": round(total, 1),
                       "results": common.RESULTS}, f, indent=1)
        print(f"# wrote {len(common.RESULTS)} rows to {args.json}",
              file=sys.stderr)
    print(f"# total {total:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
