"""Record the small trace that ``test_trace.py`` reduces: one traced run of a
cell on the chip, cut to the first ``--keep-ms`` of its window.

    python3 bench/tests/record_trace.py --workload bad51.drain --seed 1 \
        --seconds 3 --keep-ms 400 --out bad51_drain.json.gz
"""
import argparse
import gzip
import json
import pathlib
import sys
import time

sys.path[:0] = [str(pathlib.Path(__file__).resolve().parents[2]),
                str(pathlib.Path(__file__).resolve().parents[2] / "src")]


def cut(tr: dict, keep_ns: float) -> dict:
    """The window's first ``keep_ns``: its events, and a window span that
    ends there."""
    w0 = min(h[1] for h in tr["host"] if h[0] == "window")
    end = w0 + keep_ns
    host = [h for h in tr["host"] if h[0] != "window" and h[1] < end]
    host.append(["window", w0, keep_ns])
    dev = {p: {ln: [e for e in evs if w0 <= e[1] < end]
               for ln, evs in lines.items()}
           for p, lines in tr["device"].items()}
    return {"device": dev, "host": host}


def main() -> int:
    from bench import run
    from bench import trace as tr
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep-ms", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    kept = {}
    real, real_load = tr.reduce, tr.load

    def keep(loaded, chips=1):
        kept["trace"] = loaded
        return real(loaded, chips)

    def survey(path):
        """Every plane and line of the raw trace, with a few events and
        their stats: what the reduction's names are checked against."""
        from jax.profiler import ProfileData
        planes = []
        for p in ProfileData.from_file(path).planes:
            lines = []
            for ln in p.lines:
                evs = list(ln.events)
                lines.append({"line": ln.name, "events": len(evs), "first": [
                    [e.name, e.duration_ns,
                     [[k, str(v)[:120]] for k, v in e.stats]]
                    for e in evs[:4]]})
            planes.append({"plane": p.name, "lines": lines})
        with open(args.out + ".survey.json", "w") as f:
            json.dump(planes, f, indent=1)
        return real_load(path)

    tr.reduce, tr.load = keep, survey
    out = run.run_cell(run.cell(args.workload), args.seed, args.seconds,
                       True, time.perf_counter())
    small = cut(kept["trace"], args.keep_ms * 1e6)
    red = real(small)
    with gzip.open(args.out, "wt") as f:
        json.dump(small, f)
    print(json.dumps({"full": out["metrics"], "device": out["device"],
                      "cut": {"window_s": red.window_s, "busy_s": red.busy_s,
                              "modules": red.modules,
                              "custom": red.custom}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
