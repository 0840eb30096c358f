"""Record the small trace that ``test_spans.py`` reduces: one traced run of a
cell on the chip, loaded by ``bench/spans.py`` (so the engine's stage scopes,
host spans and counters are kept), cut to the first ``--keep-ms`` of its
window. It prints the whole window's reduction and the cut's.

    python3 bench/tests/record_scoped_trace.py --workload bad51.drain \
        --seed 1 --seconds 8 --keep-ms 7000 \
        --out bad51_drain_v5e_scoped.json.gz
"""
import argparse
import gzip
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1]), str(HERE.parents[1] / "src")]


def main() -> int:
    from record_trace import cut

    from bench import run, spans
    from bench import trace as tr
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep-ms", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    kept = {}

    def load_dir(d):
        kept["trace"] = spans.load_any(d)
        return kept["trace"]

    tr.load_dir = load_dir
    out = run.run_cell(run.cell(args.workload), args.seed, args.seconds,
                       True, time.perf_counter())
    full = kept["trace"]
    small = cut(full, args.keep_ms * 1e6)
    mods = {e[0] for lines in small["device"].values()
            for e in lines.get(tr.MODULES_LINE, [])}
    small["scopes"] = {k: v for k, v in full["scopes"].items()
                       if k.rsplit(":", 1)[0] in mods}
    with gzip.open(args.out, "wt") as f:
        json.dump(small, f)

    def summary(t):
        sp = spans.reduce(t)
        n = sp.base.modules.get("jit_run", [0])[0]
        return {"jit_run": sp.base.modules.get("jit_run"),
                "metrics": spans.metrics(sp, n), "scope_s": sp.scope_s,
                "counters": sp.counters, "breakdown": sp.breakdown()}

    print(json.dumps({"full": out["metrics"], "device": out["device"],
                      "spans_full": summary(full),
                      "spans_cut": summary(small)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
