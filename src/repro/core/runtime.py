"""§Pipelined tick runtime: overlap host control-plane work with in-flight
device execution.

The synchronous tick loop serializes host and device: ``execute_all``
blocks per plan-group, materializes every stat eagerly, and only then lets
the next tick's aggregator/churn numpy work start. JAX dispatch is
asynchronous and per-device execution is in-order, so none of that waiting
is necessary: ``BADEngine.dispatch_all`` enqueues every plan-group's fused
call and returns device-array HANDLES immediately; this module schedules
when those handles are finally read.

``PendingExecution`` is one dispatched tick: an idempotent ``sync()``
materializes its per-channel ``ExecutionReport``s (the first host read of
the call's outputs) and runs the host half of delivery accounting.
``TickPipeline`` keeps a bounded window of them in flight — ``step`` at
depth N dispatches tick t while ticks t-1..t-(N-1) are still executing, and
only syncs the oldest when the window would exceed N-1 pending entries. The
control-plane work between ``step`` calls (subscription churn, batch
synthesis, ingest) therefore runs concurrently with the previous ticks'
joins and delivery.

Correctness under deferral: device results are dispatch-ordered and
bit-identical to the synchronous schedule (rings thread device-side from
dispatch to dispatch; watermarks advance at dispatch), so the ONLY thing
that moves in time is the host SpillQueue. Deferred captures use the
queue's epoch-free RESOLVED lane (``dispatch_all(resolve_spills=True)``):
pair fanout is resolved at sync against the dispatch-time sID tables, so
draining every ``drain_every`` ticks delivers the identical notification
multiset as the synchronous drain-every-tick path — including under
same-channel churn during sustained overflow.
"""
from __future__ import annotations

from collections import deque
from typing import Dict, List, Optional, Protocol, Tuple, runtime_checkable

from jax.profiler import TraceAnnotation


@runtime_checkable
class EngineProtocol(Protocol):
    """The shared ``BADEngine`` / ``ShardedBADEngine`` control surface.

    Everything the tick drivers — ``TickPipeline``, ``core/churn.run_ticks``,
    and the benchmark harnesses — call on "an engine", extracted so they
    type-check against ONE interface instead of duck-typing two classes.
    Both engines satisfy it structurally (asserted by tests/test_enrich.py);
    new driver code should annotate against this, not a concrete engine.

    The contract mirrors the single-device semantics: ``dispatch`` /
    ``dispatch_all`` return a pending handle with an idempotent ``sync()``
    (``ShardedPendingExecution`` merges per-shard reports), ``execute`` /
    ``execute_all`` are their synchronous composition, and the spill/ring
    surface drains per-channel regardless of placement."""

    def create_channel(self, spec) -> None: ...

    def subscribe_bulk(self, channel: str, params) -> None: ...

    def remove_subscriptions(self, channel: str, sids) -> None: ...

    def ingest(self, batch) -> None: ...

    def execute(self, request) -> Dict: ...

    def dispatch(self, request): ...

    def execute_all(self, flags=None, advance: bool = True,
                    timed: bool = True, deliver: bool = False) -> Dict: ...

    def dispatch_all(self, flags=None, advance: bool = True,
                     timed: bool = False, deliver: bool = False,
                     resolve_spills: bool = False): ...

    def drain_spilled(self, channel=None, max_entries=None) -> Dict: ...

    def flush_rings(self) -> None: ...

    def ring_pending_pairs(self, channel: str) -> int: ...

    def ring_pending_sids(self, channel: str) -> int: ...

    def set_plan(self, channel: str, plan) -> None: ...

    def set_enrichment(self, stage) -> bool: ...

    def default_plan(self): ...


class PendingExecution:
    """One dispatched ``dispatch_all`` call awaiting materialization.

    ``sync()`` is idempotent: the first call blocks on the device results,
    runs the host half (report assembly, SpillQueue pushes, conserving
    DeliveryStats) and caches the reports; later calls return them. The
    first sync runs in a ``bad.sync`` host span with one
    ``bad.sync.group`` per plan-group."""

    def __init__(self, engine, groups: List):
        self._engine = engine
        self._groups = groups
        self._reports: Optional[Dict] = None

    @property
    def done(self) -> bool:
        return self._reports is not None

    def sync(self) -> Dict:
        if self._reports is None:
            reports: Dict = {}
            with TraceAnnotation("bad.sync"):
                for g in self._groups:
                    with TraceAnnotation("bad.sync.group"):
                        self._engine._materialize_group(g, reports)
            self._reports = reports
        return self._reports

    @property
    def reports(self) -> Dict:
        return self.sync()


class TickPipeline:
    """Bounded-depth pipeline of engine ticks.

    ``depth`` is the maximum number of ticks simultaneously in flight
    (depth 1 degenerates to the synchronous schedule: every ``step`` syncs
    its own dispatch). ``drain_every`` batches ``drain_spilled`` host
    round-trips every K ticks (default: K == depth) — ``drain_due()``
    tells the driver when; conservation holds because deferred captures go
    through the SpillQueue's resolved lane.

    ``step`` returns the (tick_number, reports) pairs that became ready,
    oldest first — possibly empty while the window fills. ``flush()``
    syncs everything still in flight (end of run, or before an operation
    that must observe a quiesced engine). ``max_in_flight`` is the measured
    pipeline depth actually achieved. ``step`` and ``flush`` run in the
    ``bad.step`` and ``bad.flush`` host spans."""

    def __init__(self, engine: EngineProtocol, depth: int = 2,
                 drain_every: Optional[int] = None):
        if depth < 1:
            raise ValueError(f"pipeline depth must be >= 1, got {depth}")
        self.engine = engine
        self.depth = depth
        self.drain_every = drain_every or depth
        self._window: deque = deque()   # (tick_number, PendingExecution)
        self._tick = 0
        self.max_in_flight = 0

    @property
    def in_flight(self) -> int:
        return len(self._window)

    def step(self, flags=None, deliver: bool = True,
             timed: bool = False) -> List[Tuple[int, Dict]]:
        """Dispatch one tick; sync (only) what the depth bound forces out."""
        with TraceAnnotation("bad.step"):
            pend = self.engine.dispatch_all(flags, timed=timed,
                                            deliver=deliver,
                                            resolve_spills=True)
            self._window.append((self._tick, pend))
            self._tick += 1
            # the new dispatch overlaps with every older in-flight tick
            self.max_in_flight = max(self.max_in_flight, len(self._window))
            out: List[Tuple[int, Dict]] = []
            while len(self._window) > self.depth - 1:
                t, p = self._window.popleft()
                out.append((t, p.sync()))
            return out

    def flush(self) -> List[Tuple[int, Dict]]:
        """Sync every in-flight tick, oldest first."""
        out: List[Tuple[int, Dict]] = []
        with TraceAnnotation("bad.flush"):
            while self._window:
                t, p = self._window.popleft()
                out.append((t, p.sync()))
        return out

    def drain_due(self) -> bool:
        """True when the batched-drain cadence has come around: the driver
        should loop ``engine.drain_spilled()`` until the queue empties."""
        return self._tick % self.drain_every == 0
