"""The two ways a traffic mix drives the engine, chosen by its ``loop`` key.

``closed``: a backlog of executions of ``execution_records`` records each,
ingested and executed back to back through ``TickPipeline``; the next
execution starts as soon as the pipeline takes it. The backlog is a pool of
``pool_executions`` distinct batches from the seed, taken in turn, so that a
faster program never runs out of work.

``open``: records arrive at the configuration's ``records_per_s`` on the
generator's schedule, whatever the engine does; every ``period_s`` one execution ingests the
records created in that period and runs. Its records' creation times are the
schedule's, so a late execution counts the wait of every record in it. When
the engine is ahead of the schedule the loop materialises the execution at
once instead of leaving it in the pipeline until the next period.

Both run ``TickPipeline`` at depth ``DEPTH``. Both record, per execution,
its counts and delivery stats for the check, and keep the full reports of a
seeded sample of executions. After the window, ``replay`` runs the first
``WIRE_EXECUTIONS`` of that sample again through the same engine with its
delivery buffers copied to the host, so that the check can read what the
broker stage wrote.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Dict, List, Optional

import numpy as np
from jax import profiler

from bench import deploy, generator
from repro.core.runtime import TickPipeline

Span = profiler.TraceAnnotation
# bounded drain rounds: each round re-delivers or requeues at the front, so
# an empty queue is reached long before this; a queue that never empties is
# a fault the check reports, not a hang
MAX_DRAIN_ROUNDS = 10_000
# executions in flight: the pipeline depth the served path runs at
DEPTH = 2
# sampled executions whose delivery buffers the check decodes; each costs one
# execution and a host copy of its wire buffers (~0.7 GB at the §5.1 sizes)
WIRE_EXECUTIONS = 3


@dataclasses.dataclass
class Execution:
    k: int                       # execution number in the window
    batch: int                   # index into CellData.batches
    row0: int                    # global row id of its first record
    records: int
    due: Optional[float]         # open loop: when its last record existed
    done: float = 0.0            # host clock when its reports materialised
    counts: Dict = dataclasses.field(default_factory=dict)
    stats: Dict = dataclasses.field(default_factory=dict)
    pairs: Optional[Dict] = None  # sampled: name -> (rows, targets, valid)
    # replayed: name -> (wire lines, notified sIDs), delivered prefixes
    wire: Optional[Dict] = None


def sampled(seed: int, traffic: Dict) -> set:
    """The executions whose full pair sets the check compares: each with
    probability ``check_share``, at most ``check_max``, drawn from the seed."""
    mask = generator.rng_for(seed, generator.SAMPLE).random(1 << 16)
    return set(np.flatnonzero(mask < traffic["check_share"])
               [:traffic["check_max"]].tolist())


def warm_batches(cfg: Dict, batches: List[tuple]) -> List[int]:
    """One batch of each size class the window's executions reach (the
    window takes only these batches): the
    engine compiles its fused call per power of two of the largest
    per-channel count of new BAD-index entries (``generator.size_class``)."""
    first = {}
    for i, (f, _) in enumerate(batches):
        first.setdefault(generator.size_class(cfg, f), i)
    return [first[c] for c in sorted(first)]


class Loop:
    """Runs one cell's executions and keeps what the check and the metrics
    read. ``row`` follows the engine's row ids (preload first)."""

    def __init__(self, eng, cfg: Dict, traffic: Dict, data, seed: int):
        self.eng, self.cfg, self.traffic, self.data = eng, cfg, traffic, data
        self.row = cfg["preload_records"]
        self.execs: List[Execution] = []
        self.replays: List[Execution] = []
        self.lateness: List[float] = []   # open loop: start minus due
        # sIDs and pairs delivered by spill drains, per channel
        self.drained = collections.defaultdict(collections.Counter)
        self.sampled = sampled(seed, traffic)

    def execute(self, pipe, into: List[Execution], k: int, b: int,
                due: Optional[float] = None) -> None:
        """Ingest batch ``b`` as execution ``k`` and step the pipeline."""
        f, l = self.data.batches[b]
        into.append(Execution(k, b, self.row, f.shape[0], due))
        with Span("ingest"):
            self.eng.ingest(deploy.batch(f, l))
        self.row += f.shape[0]
        with Span("step"):
            self.take(pipe.step(None, deliver=True), into)

    def take(self, ready, into: List[Execution]) -> None:
        now = time.perf_counter()
        for tick, reports in ready:
            ex = into[tick]
            ex.done = now
            for name, rep in reports.items():
                ex.counts[name] = (rep.num_results, rep.num_notified)
                ex.stats[name] = rep.overflow
                if rep.payload is not None:
                    s = rep.overflow
                    ex.wire = ex.wire or {}
                    ex.wire[name] = (
                        np.array(rep.payload[:s.delivered_pairs]),
                        np.array(rep.notify[:s.delivered_sids]))
            if ex.k in self.sampled and into is self.execs:
                ex.pairs = {name: (rep.result.pair_rows,
                                   rep.result.pair_targets,
                                   rep.result.pair_valid)
                            for name, rep in reports.items()}

    def drain(self, acc=None) -> None:
        """Drain the spill queue to empty."""
        acc = self.drained if acc is None else acc
        spill = self.eng.spill
        with Span("drain"):
            for _ in range(MAX_DRAIN_ROUNDS):
                if spill.pending_pairs() + spill.pending_sids() == 0:
                    break
                for key, rep in self.eng.drain_spilled().items():
                    acc[key.split("@")[0]].update(
                        delivered_pairs=rep.stats.delivered_pairs,
                        delivered_sids=rep.stats.delivered_sids)

    def settle(self, acc=None) -> None:
        """Hand ring entries to the spill queue and drain it to empty."""
        self.eng.flush_rings()
        self.drain(acc)

    def warm(self) -> None:
        """Run the warm-up executions (every shape the window will use),
        then leave the engine with nothing in flight, in a ring or queued."""
        pipe = TickPipeline(self.eng, depth=DEPTH)
        scratch: List[Execution] = []
        for b in warm_batches(self.cfg, self.data.batches):
            self.execute(pipe, scratch, -1, b)
        self.take(pipe.flush(), scratch)
        self.settle(collections.defaultdict(collections.Counter))

    def finish(self) -> None:
        """After the window: deliver what is left in rings and the queue."""
        self.settle()

    def replay(self) -> None:
        """After ``finish``: run the batches of the first ``WIRE_EXECUTIONS``
        sampled executions again, through the same engine and the programs
        the window ran, with the delivery buffers copied to the host (the
        engine's ``debug_delivery_buffers`` changes only its host half).
        The copies are kept in ``replays``; their deliveries count nowhere
        else."""
        n = len(self.data.batches)
        batches = sorted({k % n for k in self.sampled})[:WIRE_EXECUTIONS]
        pipe = TickPipeline(self.eng, depth=DEPTH)
        self.eng.debug_delivery_buffers = True
        for k, b in enumerate(batches):
            self.execute(pipe, self.replays, k, b)
        self.take(pipe.flush(), self.replays)
        self.eng.debug_delivery_buffers = False
        self.settle(collections.defaultdict(collections.Counter))


class ClosedLoop(Loop):
    def window(self, seconds: float) -> tuple:
        pipe = TickPipeline(self.eng, depth=DEPTH)
        t_first = time.perf_counter()
        deadline = t_first + seconds
        with Span("window"):
            k = 0
            while time.perf_counter() < deadline:
                self.execute(pipe, self.execs, k,
                             k % len(self.data.batches))
                if pipe.drain_due():
                    self.drain()
                k += 1
            with Span("flush"):
                self.take(pipe.flush(), self.execs)
        return t_first, time.perf_counter()


class OpenLoop(Loop):
    def window(self, seconds: float) -> tuple:
        pipe = TickPipeline(self.eng, depth=DEPTH)
        period = self.traffic["period_s"]
        t_first = time.perf_counter()
        with Span("window"):
            for k in range(len(self.data.batches)):
                due = t_first + (k + 1) * period
                wait = due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                self.lateness.append(time.perf_counter() - due)
                self.execute(pipe, self.execs, k, k, due)
                if pipe.drain_due():
                    self.drain()
                if time.perf_counter() < due + period:
                    with Span("flush"):
                        self.take(pipe.flush(), self.execs)
            with Span("flush"):
                self.take(pipe.flush(), self.execs)
        return t_first, time.perf_counter()


def plan(cfg: Dict, traffic: Dict, seconds: float) -> tuple:
    """(loop class, records per execution, batches to generate)."""
    if traffic["loop"] == "closed":
        return ClosedLoop, cfg["execution_records"], \
            traffic["pool_executions"]
    if traffic["loop"] == "open":
        period = traffic["period_s"]
        n = int(round(cfg["records_per_s"] * period))
        return OpenLoop, n, max(1, int(math.floor(seconds / period)))
    raise ValueError(f"unknown loop {traffic['loop']!r}")
