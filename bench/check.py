"""The comparison that decides ``correct``, and the open loop's latencies.

Every number below is compared with its limit; the run is correct when each
is at or under it. All are exact comparisons, so every limit is 0 (PERF.md
gives the readings of sound runs and of the control they were set from).

- ``counts_off``: over every execution of the window and every channel,
  |results - reference| + |notified - reference|.
- ``pairs_off``: over a seeded sample of executions, the multiset symmetric
  difference between the engine's (record, group) / (record, user) pairs and
  the reference's.
- ``table_off``: subscribers or users missing, duplicated or misfiled in
  the engine's delivery tables (``reference.check_tables``).
- ``conservation_off``: over every execution and channel,
  |delivered + spilled + dropped - produced - retried|, pairs and sIDs.
- ``broker_off``: over executions with nothing spilled or retried,
  |delivered pairs per broker - reference|.
- ``wire_off``: over the replayed executions (``loops.Loop.replay``) and
  every channel, the multiset symmetric difference between the (record, sID)
  notifications that the convert stage's wire lines carry and the
  reference's, plus every line whose header or payload words disagree with
  the line's record, or that names an sID outside the channel's.
- ``notify_off``: the same for the send stage's flat sID buffer, each sID
  taken as the notification of the record whose wire line covers its
  position, plus the difference in length between the two.
- ``lost_sids`` / ``lost_pairs``: produced in the window minus delivered by
  the window's executions and spill drains and the drain after it; dropped
  notifications are among them.
- ``missing``: executions dispatched whose reports never came.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

from bench import reference

LIMITS = {"counts_off": 0, "pairs_off": 0, "table_off": 0,
          "conservation_off": 0, "broker_off": 0, "wire_off": 0,
          "notify_off": 0, "lost_sids": 0, "lost_pairs": 0, "missing": 0}
# a wire line: [record, target, member count, payload words], the target's
# sIDs (member-count prefix meaningful), then the payload words, each the
# record's id (the program's broker wire format)
HEADER_WORDS = 4


@dataclasses.dataclass
class Result:
    values: Dict[str, int]
    attempted: int
    failed: int
    notifying: int               # records that notified someone

    @property
    def correct(self) -> bool:
        return all(self.values[k] <= lim for k, lim in LIMITS.items())

    def as_dict(self) -> Dict:
        return {k: {"value": self.values[k], "limit": lim}
                for k, lim in LIMITS.items()}

    def lines(self) -> List[str]:
        return [f"check {k}: {self.values[k]} (limit {lim})"
                for k, lim in LIMITS.items()]


def _answers(ctx, data, ex, want_keys: bool, cache: Dict):
    if not want_keys and ex.batch in cache:
        return cache[ex.batch]
    f, l = data.batches[ex.batch]
    a = reference.answers(ctx, f, l, ex.row0, want_keys)
    if not want_keys:
        cache[ex.batch] = a
    return a


def wire_off(lines: np.ndarray, notify: np.ndarray, cap: int, space: int,
             want: np.ndarray) -> tuple:
    """(wire_off, notify_off) of one channel's delivered wire lines
    ``(n, HEADER_WORDS + cap + payload words)`` and notified sIDs against the
    reference's sorted (record, sID) keys ``want``, ``record * space + sID``."""
    lines = np.asarray(lines, np.int64).reshape(-1, lines.shape[-1])
    notify = np.asarray(notify, np.int64).ravel()
    rows, members = lines[:, 0], lines[:, 2]
    words = lines[:, HEADER_WORDS + cap:]
    bad = ((members < 0) | (members > cap)
           | (lines[:, 3] != words.shape[1])
           | (words != rows[:, None]).any(axis=1))
    members = np.clip(members, 0, cap)
    sids = lines[:, HEADER_WORDS:HEADER_WORDS + cap][
        np.arange(cap)[None, :] < members[:, None]]
    by_line = np.repeat(rows, members)
    out_w = int(bad.sum()) + int(((sids < 0) | (sids >= space)).sum())
    out_w += reference.multiset_off(by_line * space + sids, want)
    m = min(by_line.size, notify.size)
    n = notify[:m]
    out_n = abs(by_line.size - notify.size) + int(
        ((n < 0) | (n >= space)).sum())
    out_n += reference.multiset_off(by_line[:m] * space + n, want)
    return out_w, out_n


def compare(ctx, data, drv, table_off: int) -> Result:
    v = dict.fromkeys(LIMITS, 0)
    v["table_off"] = table_off
    produced = {"sids": 0, "pairs": 0}
    delivered = {"sids": 0, "pairs": 0}
    cache: Dict = {}
    notifying = 0
    names = [ch["name"] for ch in ctx.cfg["channels"]]
    for ex in drv.execs:
        if set(ex.counts) != set(names):
            v["missing"] += 1
            continue
        ref = _answers(ctx, data, ex, ex.pairs is not None, cache)
        notifying += np.unique(np.concatenate(
            [a.rows for a in ref.values()])).size
        for name in names:
            nr, nn = ex.counts[name]
            a, s = ref[name], ex.stats[name]
            v["counts_off"] += abs(nr - a.num_results) + abs(
                nn - a.num_notified)
            v["conservation_off"] += abs(
                s.delivered_pairs + s.spilled_pairs + s.dropped_pairs
                - nr - s.retried_pairs) + abs(
                s.delivered_sids + s.spilled_sids + s.dropped_sids
                - nn - s.retried_sids)
            if not (s.spilled_pairs or s.dropped_pairs or s.retried_pairs
                    or s.spilled_sids or s.dropped_sids or s.retried_sids):
                got = np.asarray(s.delivered_pairs_broker or
                                 [0] * a.broker_pairs.size)
                v["broker_off"] += int(np.abs(got - a.broker_pairs).sum())
            produced["sids"] += nn
            produced["pairs"] += nr
            delivered["sids"] += s.delivered_sids
            delivered["pairs"] += s.delivered_pairs
            if ex.pairs is not None:
                rows, tgts, valid = ex.pairs[name]
                got = reference.observed_keys(ctx, name, np.asarray(rows),
                                              np.asarray(tgts),
                                              np.asarray(valid))
                v["pairs_off"] += reference.multiset_off(got, a.keys)
    if not drv.replays:
        v["missing"] += 1
    for ex in drv.replays:
        if ex.wire is None or set(ex.wire) != set(names):
            v["missing"] += 1
            continue
        f, l = data.batches[ex.batch]
        ref = reference.answers(ctx, f, l, ex.row0, False, want_sids=True)
        for ch in ctx.cfg["channels"]:
            name = ch["name"]
            cap = 1 if ch["join"] == "spatial" else ctx.cfg["group_cap"]
            w, n = wire_off(*ex.wire[name], cap,
                            ctx.tables[name].sid_space, ref[name].sid_keys)
            v["wire_off"] += w
            v["notify_off"] += n
    for name, acc in drv.drained.items():
        delivered["sids"] += acc["delivered_sids"]
        delivered["pairs"] += acc["delivered_pairs"]
    v["lost_sids"] = abs(produced["sids"] - delivered["sids"])
    v["lost_pairs"] = abs(produced["pairs"] - delivered["pairs"])
    # dropped notifications are among those never delivered
    failed = max(0, produced["sids"] - delivered["sids"])
    return Result(v, produced["sids"], failed, notifying)


def latencies_ms(ctx, data, drv) -> List[float]:
    """Open loop: for every record that notified someone, the time from its
    creation on the generator's schedule to the materialisation of the
    execution that delivered it."""
    rate = drv.cfg["records_per_s"]
    period = drv.traffic["period_s"]
    out: List[float] = []
    for ex in drv.execs:
        if not ex.done:
            continue
        f, l = data.batches[ex.batch]
        ref = reference.answers(ctx, f, l, ex.row0, want_keys=False)
        rows = np.unique(np.concatenate([a.rows for a in ref.values()]))
        created = ex.due - period + rows / rate
        out.extend(((ex.done - created) * 1e3).tolist())
    return out
