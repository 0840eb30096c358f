"""The control of the comparison that decides ``correct``.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...]

The reference is put in the engine's place one precision step below what
the configuration states: record fields narrowed to int16, and locations
and squared distances computed in bfloat16 with ``jax.numpy`` on the
default device (the chip, where there is one). Its answers stand in for a
run's executions, replays and delivered wire buffers, as if everything
produced were delivered, and go through ``check.compare`` against the
reference at the stated precision, over the executions a run at
``--seconds`` makes (for a closed loop, enough passes over the pool to reach
every sampled one) and the same seeded sample of them. A sound comparison
must read the control as not correct; PERF.md keeps the readings. The
benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time
import types

ROOT = pathlib.Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

# the precision steps the control takes, each alone and together
LOWER = (("fields",), ("locations",), ("fields", "locations"))
# payload words of a stand-in wire line (the check reads them off its width)
PAYLOAD_WORDS = 8


@dataclasses.dataclass
class Delivered:
    """The delivery stats of an execution that delivered all it produced."""

    delivered_pairs: int
    delivered_sids: int
    delivered_pairs_broker: tuple
    spilled_pairs: int = 0
    dropped_pairs: int = 0
    retried_pairs: int = 0
    spilled_sids: int = 0
    dropped_sids: int = 0
    retried_sids: int = 0


def _wire(ctx, raw, name: str, keys):
    """Wire lines and notified sIDs for one channel's (record, target) keys,
    laid out as the program's broker stage lays them out."""
    import numpy as np

    from bench.check import HEADER_WORDS
    tb = ctx.tables[name]
    if hasattr(tb, "uid_of_slot"):
        nu = ctx.user_locs.shape[0]
        rows, tgts = keys // nu, keys % nu
        sids = tgts[:, None]
    else:
        g = tb.slot_param.size
        rows, tgts = keys // g, keys % g
        sids = np.asarray(raw[name])[tgts]
    members = (sids >= 0).sum(axis=1)
    lines = np.concatenate(
        [np.stack([rows, tgts, members,
                   np.full_like(rows, PAYLOAD_WORDS)], axis=1),
         sids, np.repeat(rows[:, None], PAYLOAD_WORDS, axis=1)], axis=1)
    assert lines.shape[1] == HEADER_WORDS + sids.shape[1] + PAYLOAD_WORDS
    return lines, sids[sids >= 0]


def stand_in(c, ctx, raw, data, seed: int, n: int, lower: tuple):
    """What a run's loop would hold if the lower-precision reference had
    served its ``n`` executions and replays."""
    import numpy as np

    from bench import loops, reference
    cfg = c.cfg
    sampled = loops.sampled(seed, c.traffic)
    nb = len(data.batches)
    replay = sorted({k % nb for k in sampled})[:loops.WIRE_EXECUTIONS]
    execs, replays = [], []
    row0 = cfg["preload_records"]
    for into, batches in ((execs, [k % nb for k in range(n)]),
                          (replays, replay)):
        for k, b in enumerate(batches):
            f, l = data.batches[b]
            want_pairs = into is execs and k in sampled
            want_wire = into is replays
            low = reference.answers(ctx, f, l, row0, want_pairs or want_wire,
                                    lower=lower)
            ex = loops.Execution(k, b, row0, f.shape[0], None)
            for name, a in low.items():
                ex.counts[name] = (a.num_results, a.num_notified)
                ex.stats[name] = Delivered(
                    a.num_results, a.num_notified,
                    tuple(int(x) for x in a.broker_pairs))
            if want_pairs:
                ex.pairs = {}
                for name, a in low.items():
                    g = ctx.tables[name]
                    space = g.slot_param.size if hasattr(g, "slot_param") \
                        else ctx.user_locs.shape[0]
                    ex.pairs[name] = (a.keys // space, a.keys % space,
                                      np.ones(a.keys.size, bool))
            if want_wire:
                ex.wire = {name: _wire(ctx, raw, name, a.keys)
                           for name, a in low.items()}
            into.append(ex)
            row0 += f.shape[0]
    return types.SimpleNamespace(execs=execs, replays=replays, drained={})


def control(c, seed: int, seconds: float) -> dict:
    """Each lower precision step's compared numbers and ``correct``."""
    import numpy as np

    from bench import check, generator, loops, reference
    cfg, traffic = c.cfg, c.traffic
    _, per_exec, n_batches = loops.plan(cfg, traffic, seconds)
    data = generator.make(cfg, per_exec, n_batches, seed)
    raw = reference.own_tables(cfg, data.sub_params, data.sub_brokers,
                               data.user_locs.shape[0])
    sids = {ch["name"]: np.arange(data.sub_params.size)
            for ch in cfg["channels"]}
    ctx, table_off = reference.check_tables(
        cfg, data.sub_params, data.sub_brokers, sids, raw, data.user_locs,
        data.user_brokers)
    assert table_off == 0, table_off
    sampled = loops.sampled(seed, traffic)
    # a closed loop cycles its pool for as long as the window lasts: take
    # enough executions to reach every sampled one
    n = n_batches if traffic["loop"] == "open" else max(
        n_batches, max(sampled) + 1)
    out = {}
    for lower in LOWER:
        drv = stand_in(c, ctx, raw, data, seed, n, lower)
        r = check.compare(ctx, data, drv, table_off)
        out["+".join(lower)] = dict(r.values, correct=r.correct)
    out["executions"] = n
    out["sampled"] = len(sampled & set(range(n)))
    return out


def main(argv=None) -> int:
    from bench import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float,
                    default=run.load_json(ROOT / "BENCHMARK.json")
                    ["run_seconds"])
    args = ap.parse_args(argv)
    c = run.cell(args.workload)
    import jax
    dev = jax.devices()[0]
    for seed in args.seeds:
        t = time.perf_counter()
        v = control(c, seed, args.seconds)
        print(json.dumps({"workload": c.name, "seed": seed, "control": v,
                          "seconds": time.perf_counter() - t,
                          "device": dev.device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
