"""BADEngine: the host-side orchestrator tying the data plane together.

Responsibilities (paper Fig. 1): data feed ingestion -> ActiveDataset append +
conditionsList evaluation + BAD-index maintenance; channel execution under a
chosen ``ExecutionFlags`` plan; broker accounting; subscription control plane
(Algorithm 1 grouping + UserParameters upkeep).

The engine is deliberately a thin host shell: every per-record code path is a
jitted pure function over fixed-shape arrays.

``use_pallas=True`` routes every predicate / spatial evaluation through the
Pallas kernels (``predicate_filter`` at ingestion AND inside the fused
executor's candidate discovery; ``spatial_match`` in both spatial join
paths); the default jnp oracle is the parity reference, and the two are
result-identical by construction (asserted by the parity suite).

Broker delivery (``deliver=True`` on ``execute_channel`` / ``execute_all``)
runs the broker's convert+send stages and surfaces per-stage accounting in
``ExecutionReport.overflow`` (a ``DeliveryStats``). On ``execute_all`` the
delivery is FUSED: ``broker.deliver_all`` runs inside the same jitted call as
candidate discovery and the joins, so a multi-channel tick never leaves the
device between discovery and subscriber fanout. No notification is silently
lost: pairs/sIDs that miss a delivery buffer land first in the
device-resident ``RetryRing`` (per join group) and are re-packed and
re-delivered *inside the next fused call* — sustained overflow never
round-trips through the host; only overflow past the ring window cascades —
with its channel identity — into the bounded host-side ``SpillQueue`` (the
ring's last resort) and is re-delivered exactly once by ``drain_spilled()``
on subsequent ticks. Ring pairs whose channel churned go epoch-stale and
drop (counted) instead of indexing a moved table; only window/queue
exhaustion drops, and drops are counted
(delivered + spilled + dropped == produced == fresh + retried, per stage —
an identity that telescopes across ticks).
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import bad_index as bidx
from repro.core import enrich
from repro.core import plans
from repro.core import records as R
from repro.core import subscriptions as subs
from repro.core.broker import (BrokerRegistry, DeliveryStats, FusedDelivery,
                               RetryRing, deliver_all, empty_ring,
                               fanout_sids, pack_payloads,
                               resolve_pair_sids)
from repro.core.channel import ChannelSpec
from repro.core.predicates import (CompiledConditions, compile_conditions,
                                   evaluate_conditions)
from repro.core.user_params import UserParameters


@dataclasses.dataclass
class MaintenanceStats:
    """Counters for the epoch/delta maintenance machinery.

    ``traces`` counts jit TRACES of engine-owned device functions — the
    increment sits inside the traced Python bodies, so cached executions
    never count; ``rebuilds`` counts full stacked-cache rebuilds;
    ``patches`` counts in-place delta patch applications. Steady-state churn
    should show ``patches`` advancing while ``traces`` and ``rebuilds`` stay
    flat (the churn suite asserts exactly that)."""

    traces: int = 0
    rebuilds: int = 0
    patches: int = 0

    def snapshot(self) -> "MaintenanceStats":
        return dataclasses.replace(self)

    def since(self, prior: "MaintenanceStats") -> "MaintenanceStats":
        return MaintenanceStats(self.traces - prior.traces,
                                self.rebuilds - prior.rebuilds,
                                self.patches - prior.patches)


class UserCohort:
    """Stable-slot set of global user ids subscribed to ONE spatial channel.

    Slot index == row in that channel's stacked user-set (and the pair
    target index its results carry), so cohort churn patches device rows in
    place exactly like the Aggregator's group slots; freed slots are reused,
    never leaked into padded capacity."""

    def __init__(self):
        self._uids: List[int] = []          # per slot; -1 when free
        self._slot: Dict[int, int] = {}     # live uid -> slot
        self._free: List[int] = []

    @property
    def num_slots(self) -> int:
        return len(self._uids)

    @property
    def num_users(self) -> int:
        return len(self._slot)

    def add(self, uids: np.ndarray) -> set:
        """Attach users; returns the slots touched (already-present ids are
        no-ops)."""
        touched = set()
        for u in np.asarray(uids, dtype=np.int32).ravel().tolist():
            if u in self._slot:
                continue
            if self._free:
                s = self._free.pop()
                self._uids[s] = u
            else:
                s = len(self._uids)
                self._uids.append(u)
            self._slot[u] = s
            touched.add(s)
        return touched

    def remove(self, uids: np.ndarray) -> set:
        touched = set()
        for u in np.asarray(uids, dtype=np.int32).ravel().tolist():
            s = self._slot.pop(u, None)
            if s is not None:
                self._uids[s] = -1
                self._free.append(s)
                touched.add(s)
        return touched

    def slot_uids(self) -> np.ndarray:
        """(num_slots,) int32 uid per slot, -1 holes."""
        return np.asarray(self._uids, dtype=np.int32).reshape(-1)


@dataclasses.dataclass
class ChannelState:
    spec: ChannelSpec
    index: int                      # row in the stacked conditionsList / BADIndexState
    aggregator: subs.Aggregator
    user_params: UserParameters
    # the channel's current physical plan (scan mode x layout x backend);
    # None falls back to the engine default. ``execute_all(flags=None)``
    # partitions channels into plan-groups by this value — set it via
    # ``BADEngine.set_plan`` (the runtime planner's switch point)
    plan: Optional[plans.ChannelPlan] = None
    last_exec_ts: int = 0
    last_exec_size: int = 0
    executions: int = 0
    # ``epoch`` is a total order over this channel's subscription state:
    # bumped on EVERY control-plane change. It keys spill staleness and the
    # engine's epoch-tracked device caches; ``delta_log`` holds the
    # (epoch, GroupDelta) records a cache reflecting epoch e applies to
    # catch up to the present — any gap (log overflow, out-of-band mutation)
    # forces that cache to fully rebuild instead.
    epoch: int = 0
    delta_log: Deque = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=64))
    # spatial channels: explicit subscriber cohort (None = every user, the
    # legacy global-UserLocations semantics), with its own epoch/delta log
    cohort: Optional[UserCohort] = None
    user_epoch: int = 0
    user_delta_log: Deque = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=64))
    # device-resident TargetArrays + host group/flat views, cached per
    # channel and dropped whenever the subscription set changes (the
    # per-channel path is the from-scratch reference the delta-maintained
    # stacked caches are tested against)
    _targets_flat: Optional[plans.TargetArrays] = None
    _targets_grouped: Optional[plans.TargetArrays] = None
    _groups: Optional[subs.SubscriptionGroups] = None
    _flat: Optional[subs.SubscriptionTable] = None
    _host_targets: Dict[bool, Tuple] = dataclasses.field(default_factory=dict)
    _cohort_users: Optional[Tuple] = None

    def note_change(self) -> None:
        """Advance the epoch and log the aggregator's accumulated delta so
        epoch-tracked caches can patch in place instead of rebuilding."""
        delta = self.aggregator.take_delta()
        self.epoch += 1
        self.delta_log.append((self.epoch, delta))
        self._drop_host_caches()

    def note_user_change(self, touched_slots: set) -> None:
        """Cohort churn: slots remap, so spatial pair spills go stale (epoch
        bump) and the stacked user-set cache gets a patchable delta."""
        self.epoch += 1
        self.user_epoch += 1
        self.user_delta_log.append((self.user_epoch,
                                    frozenset(touched_slots)))
        self._drop_host_caches()

    def invalidate_targets(self) -> None:
        """Out-of-band invalidation (no delta recorded): the safety hatch
        for callers that mutate the aggregator directly — every
        epoch-tracked cache sees the gap and fully rebuilds."""
        self.aggregator.take_delta()
        self.epoch += 1
        self._drop_host_caches()

    def _drop_host_caches(self) -> None:
        self._targets_flat = self._targets_grouped = None
        self._groups = self._flat = None
        self._host_targets = {}
        self._cohort_users = None


@dataclasses.dataclass
class _GroupCache:
    """Epoch-tracked stacked device targets for the fused param-join path.

    Capacity-padded (tmax slots / dmax domain / mmax fan-out / cap members)
    so shapes — and therefore the fused plan's trace — are stable across
    churn; group deltas patch rows in place and ``epochs`` records the
    per-channel subscription epoch the arrays reflect."""

    names: Tuple[str, ...]
    aggregated: bool
    epochs: List[int]
    tmax: int
    dmax: int
    mmax: int
    cap: int
    targets: plans.TargetArrays
    up_masks: jnp.ndarray           # (C, dmax) bool
    domains: jnp.ndarray            # (C,) int32
    sids: jnp.ndarray               # (C, tmax, cap) int32


@dataclasses.dataclass
class _SpatialCache:
    """Epoch-tracked stacked per-channel user sets for the fused spatial
    join; cohort deltas patch slot rows in place. ``identity`` is True when
    every channel serves the full global user set — delivery then uses the
    0-width identity fanout exactly as before cohorts existed."""

    names: Tuple[str, ...]
    user_version: int
    cohorted: Tuple[bool, ...]
    epochs: List[int]               # per-channel user_epoch reflected
    ub: int
    locs: jnp.ndarray               # (C, ub, 2) f32, -FAR holes
    brokers: jnp.ndarray            # (C, ub) int32
    uids: jnp.ndarray               # (C, ub) int32 global uid per slot, -1 holes

    @property
    def identity(self) -> bool:
        return not any(self.cohorted)


class SpillQueue:
    """Bounded host-side capture of overflowed notifications.

    Two lanes, mirroring the broker's two delivery stages: *pairs* (result
    pairs that missed the convert-stage wire buffer, keyed by channel and
    target LAYOUT — False = flat rows, True = compacted group rows,
    "slot" = aggregator slot rows — so a drain re-packs against the right
    table) and *sids* (end-subscriber ids that missed the send-stage notify
    buffer). Entries keep their channel identity; each lane is bounded by
    ``capacity`` — pushes past it are rejected (the caller counts them as
    dropped, so nothing is ever lost *silently*).

    Pair entries record the channel's subscription EPOCH at spill time:
    target indices are only meaningful against the table they were produced
    from, so a drain discards (and counts as dropped) entries whose channel
    churned in between. Raw sIDs never go stale.

    A third *resolved* lane holds pairs whose target->sID fanout was already
    resolved against the producing call's OWN table (the pipelined runtime
    materializes stats ticks after dispatch, when the live table may have
    churned past the dispatch-time epoch — resolving at capture time makes
    the entry epoch-free, so deferred batched drains deliver the identical
    multiset as the synchronous path). Resolved entries share the pairs
    lane's capacity budget and never go stale.
    """

    def __init__(self, capacity: int = 1 << 16):
        self.capacity = capacity
        self._pairs: Dict[Tuple[str, bool], Deque] = {}
        self._sids: Dict[str, Deque] = {}
        self._resolved: Dict[str, Deque] = {}
        self._n_pairs = 0
        self._n_sids = 0

    def push_pairs(self, channel: str, aggregated: bool, rows: np.ndarray,
                   targets: np.ndarray, version: int) -> int:
        """Append up to the remaining capacity; returns entries accepted."""
        n = min(len(rows), self.capacity - self._n_pairs)
        if n > 0:
            q = self._pairs.setdefault((channel, aggregated),
                                       collections.deque())
            q.append((np.asarray(rows[:n]), np.asarray(targets[:n]), version))
            self._n_pairs += n
        return max(n, 0)

    def _push_front_pairs(self, channel: str, aggregated: bool,
                          rows: np.ndarray, targets: np.ndarray,
                          version: int) -> None:
        """Requeue a just-popped tail at the FRONT (drain order preserved,
        no capacity check — the pop already released the room)."""
        if len(rows):
            q = self._pairs.setdefault((channel, aggregated),
                                       collections.deque())
            q.appendleft((np.asarray(rows), np.asarray(targets), version))
            self._n_pairs += len(rows)

    def pop_pairs(self, channel: str, aggregated: bool, n: int,
                  current_version: Optional[int]
                  ) -> Tuple[np.ndarray, np.ndarray, int]:
        """Remove up to ``n`` entries in FIFO order. Entries whose version no
        longer matches ``current_version`` are discarded and counted in the
        returned ``stale`` (they index a table that no longer exists).
        Returns (rows, targets, stale)."""
        q = self._pairs.get((channel, aggregated))
        rows, tgts, stale, taken = [], [], 0, 0
        while q and taken < n:
            r, t, v = q.popleft()
            take = min(len(r), n - taken)
            if take < len(r):
                q.appendleft((r[take:], t[take:], v))
            self._n_pairs -= take
            if v != current_version:
                stale += take
            else:
                rows.append(r[:take])
                tgts.append(t[:take])
            taken += take
        if q is not None and not q:
            del self._pairs[(channel, aggregated)]
        cat = lambda xs: (np.concatenate(xs) if xs
                          else np.zeros((0,), np.int32))
        return cat(rows), cat(tgts), stale

    def push_resolved(self, channel: str, rows: np.ndarray,
                      targets: np.ndarray, sid_rows: np.ndarray) -> int:
        """Append pre-resolved (row, target, sID-row) entries up to the
        remaining PAIR capacity; returns entries accepted. ``sid_rows`` is
        the (n, w) slice of the producing call's sID table for these
        targets (w >= 1; -1 padding never fans out)."""
        n = min(len(rows), self.capacity - self._n_pairs)
        if n > 0:
            q = self._resolved.setdefault(channel, collections.deque())
            q.append((np.asarray(rows[:n]), np.asarray(targets[:n]),
                      np.asarray(sid_rows[:n])))
            self._n_pairs += n
        return max(n, 0)

    def _push_front_resolved(self, channel: str, rows: np.ndarray,
                             targets: np.ndarray,
                             sid_rows: np.ndarray) -> None:
        if len(rows):
            q = self._resolved.setdefault(channel, collections.deque())
            q.appendleft((np.asarray(rows), np.asarray(targets),
                          np.asarray(sid_rows)))
            self._n_pairs += len(rows)

    def pop_resolved(self, channel: str, n: int
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Remove up to ``n`` resolved entries in FIFO order; sID rows from
        entries of different widths are right-padded with -1 to the widest.
        Returns (rows, targets, sid_rows)."""
        q = self._resolved.get(channel)
        rows, tgts, srows, taken = [], [], [], 0
        while q and taken < n:
            r, t, s = q.popleft()
            take = min(len(r), n - taken)
            if take < len(r):
                q.appendleft((r[take:], t[take:], s[take:]))
            self._n_pairs -= take
            rows.append(r[:take])
            tgts.append(t[:take])
            srows.append(s[:take])
            taken += take
        if q is not None and not q:
            del self._resolved[channel]
        if not rows:
            return (np.zeros((0,), np.int32), np.zeros((0,), np.int32),
                    np.zeros((0, 1), np.int32))
        w = max(s.shape[1] for s in srows)
        srows = [np.pad(s, ((0, 0), (0, w - s.shape[1])), constant_values=-1)
                 if s.shape[1] < w else s for s in srows]
        return (np.concatenate(rows), np.concatenate(tgts),
                np.concatenate(srows))

    def push_sids(self, channel: str, sids: np.ndarray) -> int:
        n = min(len(sids), self.capacity - self._n_sids)
        if n > 0:
            self._sids.setdefault(channel, collections.deque()).append(
                np.asarray(sids[:n]))
            self._n_sids += n
        return max(n, 0)

    def _push_front_sids(self, channel: str, sids: np.ndarray) -> None:
        if len(sids):
            self._sids.setdefault(channel, collections.deque()).appendleft(
                np.asarray(sids))
            self._n_sids += len(sids)

    def pop_sids(self, channel: str, n: int) -> np.ndarray:
        q = self._sids.get(channel)
        out, taken = [], 0
        while q and taken < n:
            s = q.popleft()
            take = min(len(s), n - taken)
            if take < len(s):
                q.appendleft(s[take:])
            self._n_sids -= take
            out.append(s[:take])
            taken += take
        if q is not None and not q:
            del self._sids[channel]
        return np.concatenate(out) if out else np.zeros((0,), np.int32)

    def pair_keys(self) -> List[Tuple[str, bool]]:
        return list(self._pairs.keys())

    def sid_keys(self) -> List[str]:
        return list(self._sids.keys())

    def resolved_keys(self) -> List[str]:
        return list(self._resolved.keys())

    def pending_pairs(self, channel: Optional[str] = None) -> int:
        if channel is None:
            return self._n_pairs
        return (sum(sum(len(r) for r, _, _ in q)
                    for (name, _), q in self._pairs.items()
                    if name == channel)
                + sum(len(r) for r, _, _ in self._resolved.get(channel, ())))

    def pending_sids(self, channel: Optional[str] = None) -> int:
        if channel is None:
            return self._n_sids
        return sum(len(s) for s in self._sids.get(channel, ()))

    def clear(self) -> None:
        self._pairs.clear()
        self._sids.clear()
        self._resolved.clear()
        self._n_pairs = self._n_sids = 0


@dataclasses.dataclass
class DrainReport:
    """One channel's ``drain_spilled`` round: ``stats`` accounts the retry
    (delivered = re-delivered this round, spilled = still queued, dropped =
    stale/unroutable); ``payload`` / ``notify`` are the re-packed wire buffer
    and re-sent sID buffer (delivered prefix meaningful)."""

    stats: DeliveryStats
    payload: Optional[np.ndarray] = None
    notify: Optional[np.ndarray] = None


@dataclasses.dataclass
class _PendingGroup:
    """One dispatched plan-group awaiting materialization: the fused call's
    result pytree (device handles, possibly still executing) plus everything
    the host half needs — layouts and DISPATCH-TIME epoch snapshots for
    SpillQueue tagging, and (when spills are being resolved) the
    dispatch-time stacked sID table handles, so deferred stats resolve pair
    fanout against the tables the call actually joined."""

    plan: plans.ChannelPlan
    param_chs: List
    spatial_chs: List
    res: tuple                # (res_p, res_s, del_p, del_s, tots, ranks)
    p_layout: object
    s_layout: object
    deliver: bool
    wall: float                      # timed fused wall; 0.0 when untimed
    t0: float                        # dispatch timestamp (latency fallback)
    p_epochs: List[int]
    s_epochs: List[int]
    p_sids: Optional[jnp.ndarray] = None
    s_sids: Optional[jnp.ndarray] = None


@dataclasses.dataclass
class ExecutionReport:
    channel: str
    flags: plans.ExecutionFlags
    result: plans.ChannelResult
    wall_time_s: float
    num_results: int
    num_notified: int
    scanned: int
    broker_bytes: np.ndarray
    # the full plan (flags + backend) this execution ran under; None on the
    # per-channel ``execute_channel`` path (which stays flags-driven)
    plan: Optional[plans.ChannelPlan] = None
    # broker overflow accounting; None unless executed with ``deliver=True``
    overflow: Optional[DeliveryStats] = None
    # delivered wire buffers (delivered prefix meaningful); only populated
    # by ``execute_all(deliver=True)`` on an engine with
    # ``debug_delivery_buffers`` — the conservation fuzz reads delivered
    # CONTENT, production ticks skip the device->host transfer
    payload: Optional[np.ndarray] = None
    notify: Optional[np.ndarray] = None


class BADEngine:
    def __init__(self,
                 dataset_capacity: int = 1 << 18,
                 index_capacity: int = 1 << 15,
                 max_window: int = 1 << 15,
                 max_candidates: int = 1 << 13,
                 frame_bytes: int = 40 * 1024,
                 schema: R.Schema = R.ENRICHED_TWEET_SCHEMA,
                 brokers: Tuple[str, ...] = ("BrokerA",),
                 use_pallas: bool = False,
                 group_cap: Optional[int] = None,
                 max_deliver_pairs: int = 1 << 12,
                 max_notify: int = 1 << 14,
                 deliver_payload_words: int = 8,
                 max_spill: int = 1 << 13,
                 spill_capacity: int = 1 << 16,
                 incremental: bool = True,
                 ring_capacity: int = 1 << 12,
                 enrichment: Optional[enrich.EnrichmentStage] = None):
        self.schema = schema
        self.dataset = R.ActiveDataset.create(dataset_capacity, schema)
        self.index_capacity = index_capacity
        self.max_window = max_window
        self.max_candidates = max_candidates
        self.frame_bytes = frame_bytes
        self.group_cap = group_cap or subs.cap_from_frame_bytes(frame_bytes)
        self.brokers = BrokerRegistry.create(*brokers)
        self.channels: Dict[str, ChannelState] = {}
        self.use_pallas = use_pallas
        self.max_deliver_pairs = max_deliver_pairs
        self.max_notify = max_notify
        self.deliver_payload_words = deliver_payload_words
        # device-side spill capture buffer per delivery call (flat across the
        # call's channels) and the host-side bounded retry queue
        self.max_spill = max_spill
        self.spill = SpillQueue(spill_capacity)
        # device-resident retry rings (per fused join group): overflow of a
        # fused delivery re-enters the NEXT execute_all call on device;
        # only overflow past the ring window cascades to the host SpillQueue.
        # 0 disables the ring (every overflow goes straight to the queue —
        # the pre-ring behavior, kept as the host-drain baseline)
        self.ring_capacity = ring_capacity
        self._rings: Dict = {}
        self.ring_flush_drops = 0
        self._deliver_jit: Optional[Callable] = None
        # surface delivered wire buffers on ExecutionReport (testing aid)
        self.debug_delivery_buffers = False
        self.user_locations = jnp.zeros((1, 2), dtype=jnp.float32)
        self.user_brokers = jnp.zeros((1,), dtype=jnp.int32)
        # keys the stacked-user-set cache; bumped by set_user_locations
        self._user_version = 0
        self.now = 0
        # host mirror of dataset.size, maintained by ``ingest`` — advance
        # and plan bucketing read it instead of syncing on the device scalar
        # (``int(self.dataset.size)`` would block the host on every tick)
        self.size_host = 0
        self._conds: CompiledConditions = compile_conditions([])
        self.index_state = bidx.BADIndexState.create(0, index_capacity)
        self._ingest_fn = None
        # (plan-cache key, arg-shape signature) pairs already executed once:
        # ``_warm_if_new`` warms ONLY on an actual trace-cache miss, so a
        # timed call never runs a cached executable twice
        self._warmed: set = set()
        # compiled plan caches (single-channel and fused all-channel), keyed
        # on the specs/flags they close over; cleared on channel create/drop
        self._exec_cache: Dict = {}
        # adaptive compacted-stream capacities (the "compact"/"compact_pallas"
        # backends): per plan-group pow2 buckets, grown on overflow (ONE
        # re-run — the overflowed call reports the exact pre-truncation
        # total) and halved after sustained low occupancy; a converged
        # bucket replays cached traces, preserving the zero-retrace steady
        # state. ``_stream_idle`` counts consecutive low-occupancy runs.
        self._stream_buckets: Dict = {}
        self._stream_idle: Dict = {}
        # stacked device state for execute_all: one epoch-tracked entry per
        # layout (aggregated / flat / spatial). With ``incremental`` the
        # aggregated + spatial entries are patched in place from group /
        # cohort deltas (capacity-padded shapes, so no retrace); without it
        # every epoch move rebuilds from host (the pre-churn-engine
        # behavior, kept as the benchmark baseline)
        self._stacked_cache: Dict = {}
        self.incremental = incremental
        # post-join enrichment/ranking stage (core/enrich.py): scores the
        # fused candidate slots and budget-prunes pairs before deliver_all,
        # inside the same jitted call. Its ``identity`` is stamped into the
        # dispatched plans (``ChannelPlan.scorer``) so every plan-keyed
        # cache — and the retry rings — key on the scorer too.
        self.enrichment = enrichment
        self.maintenance = MaintenanceStats()
        self._patch_groups_jit: Optional[Callable] = None
        self._patch_flat_jit: Optional[Callable] = None
        self._patch_spatial_jit: Optional[Callable] = None

    # ------------------------------------------------------------------
    # control plane
    # ------------------------------------------------------------------

    def create_channel(self, spec: ChannelSpec) -> None:
        if spec.name in self.channels:
            raise ValueError(f"channel {spec.name} exists")
        if self.size_host > 0 and spec.fixed_preds:
            # BAD indexes only see records ingested after channel creation —
            # same semantics as the paper (continuous queries over new data).
            pass
        st = ChannelState(
            spec=spec,
            index=len(self.channels),
            aggregator=subs.Aggregator(self.group_cap),
            user_params=UserParameters.create(spec.param_domain),
            last_exec_ts=self.now,
        )
        st.last_exec_size = self.size_host
        self.channels[spec.name] = st
        self._rebuild_conditions()

    def drop_channel(self, name: str) -> None:
        del self.channels[name]
        survivors = sorted(self.channels.values(), key=lambda s: s.index)
        old_rows = [st.index for st in survivors]
        for i, st in enumerate(survivors):
            st.index = i
        self._rebuild_conditions(old_rows)

    def default_plan(self) -> plans.ChannelPlan:
        """The plan channels run under until one is assigned: the default
        ExecutionFlags with the engine's kernel backend."""
        return plans.ChannelPlan(
            backend="pallas" if self.use_pallas else "oracle")

    def channel_plan(self, name: str) -> plans.ChannelPlan:
        return self.channels[name].plan or self.default_plan()

    def set_plan(self, name: str, plan: plans.ChannelPlan) -> bool:
        """Assign a channel's physical plan; returns True when it changed.

        Purely a host-side assignment: the NEXT ``execute_all(flags=None)``
        call partitions plan-groups from the new value. A switch migrates
        the old plan-group's retry-ring state through the existing
        ``flush_rings`` path (entries land in the host SpillQueue, tagged
        with the layout they were produced under, and re-deliver via
        ``drain_spilled``) — no notification is lost or misrouted across
        the switch."""
        if not isinstance(plan, plans.ChannelPlan):
            raise TypeError(f"expected ChannelPlan, got {type(plan)!r}")
        st = self.channels[name]
        if st.plan == plan:
            return False
        st.plan = plan
        return True

    def plan_assignment(self) -> Dict[str, plans.ChannelPlan]:
        """Every channel's effective plan (assigned or engine default)."""
        return {name: self.channel_plan(name) for name in self.channels}

    def set_enrichment(self,
                       stage: Optional[enrich.EnrichmentStage]) -> bool:
        """Attach (or detach, with None) the post-join enrichment stage;
        returns True when it changed.

        Purely a host-side assignment, like ``set_plan``: the NEXT fused
        dispatch stamps the stage's ``identity`` into every dispatched
        plan, so the previous plan-groups' retry rings (keyed by the
        untagged/differently tagged plans) migrate through the existing
        flush path into the host SpillQueue — no notification is lost or
        re-ranked across the switch."""
        if stage is not None and not callable(getattr(stage, "score", None)):
            raise TypeError(f"expected an EnrichmentStage, got {stage!r}")
        if self.enrichment is stage:
            return False
        self.enrichment = stage
        return True

    def subscribe(self, channel: str, param: int, broker: str = "BrokerA",
                  sid: Optional[int] = None) -> int:
        st = self.channels[channel]
        if not 0 <= param < st.user_params.domain:   # before any mutation
            raise ValueError(
                f"param {param} out of [0, {st.user_params.domain}) "
                f"for {channel}")
        bid = self.brokers.names[broker]
        sid = st.aggregator.add_subscription(param, bid, sid)
        st.user_params.add(param)
        st.note_change()
        return sid

    def subscribe_bulk(self, channel: str, params: np.ndarray,
                       brokers: np.ndarray,
                       sids: Optional[np.ndarray] = None) -> np.ndarray:
        """Bulk control-plane load through the vectorized ``aggregate`` path:
        Algorithm-1 grouping semantics with no per-subscription Python work.
        Returns the assigned sIDs.

        ``sids`` assigns EXPLICIT subscription ids instead of the
        aggregator's sequential allocation — the sharded engine
        (core/sharded.py) allocates globally and hands each shard its
        hash-owned slice, so a sID names the same subscription on every
        shard and across reshards."""
        st = self.channels[channel]
        params = np.asarray(params, dtype=np.int32).ravel()
        brokers = np.asarray(brokers, dtype=np.int32).ravel()
        # validate BEFORE mutating: a bad param/broker must not leave the
        # aggregator holding subscriptions whose refcounts were never
        # registered (or whose broker id aliases the invalid-pair sentinel)
        if params.size and (int(params.min()) < 0
                            or int(params.max()) >= st.user_params.domain):
            raise ValueError(
                f"params out of [0, {st.user_params.domain}) for {channel}")
        nb = self.brokers.num_brokers
        if brokers.size and (int(brokers.min()) < 0 or int(brokers.max()) >= nb):
            raise ValueError(f"broker ids out of [0, {nb}) for {channel}")
        if self.incremental:
            sids = st.aggregator.add_bulk(params, brokers, sids)
            st.user_params.add_bulk(params)
            st.note_change()
        else:
            # the rebuild baseline: O(S) re-aggregation (group identity not
            # preserved) + out-of-band invalidation (full cache rebuild)
            sids = st.aggregator.rebuild_bulk(params, brokers, sids)
            st.user_params.add_bulk(params)
            st.invalidate_targets()
        return sids

    def unsubscribe(self, channel: str, param: int, broker: str, sid: int) -> bool:
        st = self.channels[channel]
        ok = st.aggregator.remove_subscription(param, self.brokers.names[broker], sid)
        if ok:
            st.user_params.remove(param)
            st.note_change()
        return ok

    def remove_subscriptions(self, channel: str, sids: np.ndarray) -> int:
        """Bulk removal by sID: O(Δ) routing through the aggregator's
        sid->slot map, UserParameters refcounts decremented for every
        subscription actually removed (so the early semi-join mask can
        SHRINK as interests lapse), one epoch bump. Unknown sIDs are
        ignored; returns the number removed."""
        st = self.channels[channel]
        params = st.aggregator.remove_bulk(np.asarray(sids))
        if params.size:
            st.user_params.remove_bulk(params)
            st.note_change()
        return int(params.size)

    def subscribe_users(self, channel: str, user_ids: np.ndarray) -> int:
        """Attach users to a spatial channel's cohort. The first call
        converts the channel from the legacy all-users semantics to an
        explicit cohort holding exactly the given ids. Returns the number
        newly attached."""
        st = self.channels[channel]
        if st.spec.join != "spatial":
            raise ValueError(f"{channel} is not a spatial channel")
        uids = np.asarray(user_ids, dtype=np.int32).ravel()
        nu = self.user_locations.shape[0]
        if uids.size and (int(uids.min()) < 0 or int(uids.max()) >= nu):
            raise ValueError(f"user ids out of [0, {nu})")
        created = st.cohort is None
        if created:
            st.cohort = UserCohort()
        touched = st.cohort.add(uids)
        if touched or created:
            # cohort CREATION alone changes semantics (all-users ->
            # explicit cohort) and remaps spill target space: bump even
            # when no id was new
            st.note_user_change(touched)
        return len(touched)

    def unsubscribe_users(self, channel: str, user_ids: np.ndarray) -> int:
        """Detach users from a spatial channel's cohort (no-op for ids not
        in it). Returns the number detached."""
        st = self.channels[channel]
        if st.cohort is None:
            return 0
        touched = st.cohort.remove(np.asarray(user_ids, dtype=np.int32))
        if touched:
            st.note_user_change(touched)
        return len(touched)

    def set_user_locations(self, locations: np.ndarray,
                           brokers: Optional[np.ndarray] = None) -> None:
        self.user_locations = jnp.asarray(locations, dtype=jnp.float32)
        if brokers is None:
            brokers = np.zeros((locations.shape[0],), dtype=np.int32)
        self.user_brokers = jnp.asarray(brokers, dtype=jnp.int32)
        self._user_version += 1  # invalidate stacked user targets

    # ------------------------------------------------------------------
    # data plane: ingestion
    # ------------------------------------------------------------------

    def _rebuild_conditions(self, old_rows: Optional[List[int]] = None) -> None:
        """Recompile the conditionsList and re-shape the BAD index.

        ``old_rows[i]`` is the *previous* index row of the channel now at row
        ``i`` — surviving channels keep their own buffers/watermarks by
        identity, not by position (dropping a middle channel must not hand its
        rows to the next one).
        """
        specs = sorted(self.channels.values(), key=lambda s: s.index)
        self._conds = compile_conditions([list(s.spec.fixed_preds) for s in specs])
        old = self.index_state
        new = bidx.BADIndexState.create(len(specs), self.index_capacity)
        if old_rows is None:  # channel append: surviving rows keep positions
            old_rows = list(range(min(old.num_channels, new.num_channels)))
        assert all(0 <= r < old.num_channels for r in old_rows)
        if old_rows:
            src = jnp.asarray(old_rows, jnp.int32)
            n = len(old_rows)
            new = bidx.BADIndexState(
                new.row_ids.at[:n].set(old.row_ids[src]),
                new.counts.at[:n].set(old.counts[src]),
                new.watermarks.at[:n].set(old.watermarks[src]),
                new.overflowed.at[:n].set(old.overflowed[src]),
            )
        self.index_state = new
        self._ingest_fn = None  # shapes changed; re-trace
        self._exec_cache.clear()  # compiled plans bind conds + channel rows
        self._stream_buckets.clear()  # compact stream caps re-converge
        self._stream_idle.clear()
        # stacked caches track per-channel epochs; a same-named channel
        # re-created at epoch 0 would collide, so drop them here too
        self._stacked_cache.clear()
        self._warmed.clear()   # warm bookkeeping follows the plan caches
        # retry rings are shaped/positioned by the channel set: hand their
        # resident entries to the host queue (dropped channels drop at
        # drain time, counted) rather than silently losing them
        self.flush_rings()

    def _build_ingest(self):
        conds = self._conds
        # no channel yet (data preloaded before the first channel): nothing
        # to index, and a 0-channel predicate_filter cannot lower on a TPU
        use_pallas = self.use_pallas and conds.num_channels > 0
        maint = self.maintenance

        def ingest_step(ds, index_state, batch):
            maint.traces += 1          # Python body runs at trace time only
            ds, row_ids = _append(ds, batch)
            if use_pallas:
                from repro.kernels.predicate_filter import ops as pf_ops
                matches = pf_ops.predicate_filter(batch.fields, conds)
            else:
                matches = evaluate_conditions(batch.fields, conds)
            index_state = _insert(index_state, row_ids, matches)
            return ds, index_state, row_ids

        # steady-state ticks update the dataset + BAD index IN PLACE: the
        # previous tick's buffers are donated, so XLA aliases them into the
        # outputs instead of allocating/copying per tick. The engine never
        # re-presents a pre-ingest handle (self.dataset/index_state are
        # reassigned right here), so donation is externally invisible.
        return jax.jit(ingest_step, donate_argnums=(0, 1))

    def ingest(self, batch: R.RecordBatch) -> np.ndarray:
        """Data feed entry point: append + BAD-index maintenance (Algorithm 2).

        Host-sync free: row ids and the ``now`` watermark are derived on the
        host (``append`` assigns ``size + arange(n)`` and ``size_host``
        mirrors device size exactly), so ingest never blocks on the device
        queue — the returned ids are valid while the append is still in
        flight."""
        with TraceAnnotation("bad.ingest"):
            if self._ingest_fn is None:
                self._ingest_fn = self._build_ingest()
            n = batch.num_records
            row_ids = np.arange(self.size_host, self.size_host + n,
                                dtype=np.int32)
            self.dataset, self.index_state, _ = self._ingest_fn(
                self.dataset, self.index_state, batch)
            self.size_host += n
            if n:
                # reads the batch INPUT buffer (already materialized), not a
                # computation output — no dispatch-queue sync
                ts = np.asarray(batch.fields)[:, R.TIMESTAMP]
                self.now = max(self.now, int(ts.max()))
            return row_ids

    # ------------------------------------------------------------------
    # data plane: channel execution
    # ------------------------------------------------------------------

    def _targets_host(self, st: ChannelState, aggregated: bool) -> Tuple:
        """Host-side (numpy) join targets: (params, brokers, counts, by_param,
        by_param_count). Shared by the per-channel and stacked device caches."""
        cached = st._host_targets.get(aggregated)
        if cached is not None:
            return cached
        if aggregated:
            groups = st._groups or st.aggregator.build()
            st._groups = groups
            params = np.asarray(groups.group_params, np.int32)
            brokers = np.asarray(groups.group_brokers, np.int32)
            counts = np.asarray(groups.group_counts, np.int32)
        else:
            flat = self._flat_table(st)
            params = np.asarray(flat.params, np.int32)
            brokers = np.asarray(flat.brokers, np.int32)
            counts = np.ones_like(params)
        by_param, by_count = subs.param_to_targets(params, st.spec.param_domain)
        out = (params, brokers, counts, by_param, by_count)
        st._host_targets[aggregated] = out
        return out

    def _targets(self, st: ChannelState, aggregated: bool) -> plans.TargetArrays:
        cached = st._targets_grouped if aggregated else st._targets_flat
        if cached is None:
            p, b, c, bp, bc = self._targets_host(st, aggregated)
            cached = plans.TargetArrays(jnp.asarray(p), jnp.asarray(b),
                                        jnp.asarray(c), jnp.asarray(bp),
                                        jnp.asarray(bc))
            if aggregated:
                st._targets_grouped = cached
            else:
                st._targets_flat = cached
        return cached

    def _flat_table(self, st: ChannelState) -> subs.SubscriptionTable:
        if st._flat is None:
            groups = st._groups or st.aggregator.build()
            st._groups = groups
            st._flat = subs.flatten_groups(groups)
        return st._flat

    def _cohort_device(self, st: ChannelState) -> Tuple[jnp.ndarray,
                                                        jnp.ndarray,
                                                        jnp.ndarray]:
        """One cohort channel's device (locs, brokers, slot->uid table),
        cached on the ChannelState by (user_epoch, user_version) — the
        per-channel join AND the delivery/drain paths read the same upload."""
        key = (st.user_epoch, self._user_version)
        if st._cohort_users is not None and st._cohort_users[0] == key:
            return st._cohort_users[1]
        locs, brokers, uids = self._cohort_rows(st)
        val = (jnp.asarray(locs.reshape(-1, 2)), jnp.asarray(brokers),
               jnp.asarray(uids)[:, None])
        st._cohort_users = (key, val)
        return val

    def _channel_users(self, st: ChannelState) -> Tuple[jnp.ndarray,
                                                        jnp.ndarray]:
        """One channel's user set for the per-channel spatial join: the
        global tables when it has no cohort, else the cohort's slot-shaped
        gather (holes at the far sentinel, so slot indices — the pair
        targets — line up with the fused stacked rows)."""
        if st.spec.join != "spatial" or st.cohort is None:
            return self.user_locations, self.user_brokers
        return self._cohort_device(st)[:2]

    def _spatial_sids_table(self, st: ChannelState) -> Optional[jnp.ndarray]:
        """Slot->uid delivery table for a cohort spatial channel ((U, 1),
        -1 holes); None selects the legacy identity fanout (no cohort:
        targets already ARE global user ids)."""
        if st.cohort is None:
            return None
        return self._cohort_device(st)[2]

    def group_sids_array(self, channel: str, aggregated: bool) -> jnp.ndarray:
        st = self.channels[channel]
        if aggregated:
            groups = st._groups or st.aggregator.build()
            st._groups = groups
            return jnp.asarray(groups.group_sids)
        flat = self._flat_table(st)
        return jnp.asarray(flat.sids)[:, None]

    def _exec_fn(self, channel: str, flags: plans.ExecutionFlags,
                 spatial: bool, max_cand: Optional[int] = None,
                 backend: Optional[str] = None,
                 stream_cap: int = 0) -> Callable:
        """Compiled single-channel plan, cached by everything it closes over:
        the (frozen) spec, flags, and the channel's index row. Keying on the
        spec — not the name — means re-creating a same-named channel with new
        predicates can never be served a stale plan; the cache itself lives on
        the engine and is cleared on channel create/drop.

        ``backend`` overrides the engine backend (so plan search can time
        every backend, compact included); the compact backends run the
        single-channel pipeline as a C==1 compacted stream of ``stream_cap``
        entries. The compiled function returns ``(result, stream_total)`` —
        total is 0 on the padded backends. Returns ``(fn, key)`` so callers
        can warm through ``_warm_if_new`` on actual cache misses only."""
        st = self.channels[channel]
        backend = backend or ("pallas" if self.use_pallas else "oracle")
        key = (st.spec, flags, spatial, max_cand, st.index, backend,
               stream_cap)
        cached = self._exec_cache.get(key)
        if cached is not None:
            return cached, key
        spec = st.spec
        conds_one = compile_conditions([list(spec.fixed_preds)])
        best_pred = int(np.argmax([_pred_rank(p) for p in spec.fixed_preds])) \
            if spec.fixed_preds else 0
        max_window = self.max_window
        max_cand = max_cand or self.max_candidates
        num_brokers = self.brokers.num_brokers
        use_pallas = plans.backend_family(backend) == "pallas"
        compact = plans.is_compact(backend)
        join_fn = None
        if backend == "compact_pallas":
            from repro.kernels.join_compact import ops as jc_ops
            join_fn = jc_ops.join_pairs
        ch_idx = st.index

        maint = self.maintenance

        def run(ds, index_state, targets, up_mask, last_ts, last_size,
                user_locations, user_brokers):
            maint.traces += 1          # trace-time side effect: counts traces
            if flags.scan_mode == "full":
                cand = plans.candidates_full_scan(ds, conds_one, last_ts, max_cand)
            elif flags.scan_mode == "window":
                cand = plans.candidates_window(ds, conds_one, last_size, max_window)
            elif flags.scan_mode == "trad_index":
                cand = plans.candidates_trad_index(ds, conds_one, best_pred,
                                                   last_size, max_window, max_cand)
            else:
                cand = plans.candidates_bad_index(ds, index_state, ch_idx, max_cand)
            if compact:
                # C==1 compacted stream: same code path as the fused groups
                cand1 = jax.tree.map(lambda a: a[None], cand)
                stream = plans.compact_candidates(cand1, stream_cap)
                if spatial:
                    sj = plans.join_spatial_stream(
                        ds, stream, user_locations[None], user_brokers[None],
                        jnp.asarray([spec.spatial_radius], jnp.float32),
                        jnp.asarray([spec.payload_bytes], jnp.int32),
                        num_brokers)
                else:
                    sj = plans.join_param_stream(
                        ds, stream, jax.tree.map(lambda a: a[None], targets),
                        jnp.asarray([spec.param_field], jnp.int32),
                        jnp.asarray([spec.payload_bytes], jnp.int32),
                        num_brokers,
                        up_mask[None] if flags.param_pushdown else None,
                        flags.aggregation,
                        jnp.asarray([targets.by_param.shape[0]], jnp.int32),
                        join_fn)
                width = min(stream_cap, cand.rows.shape[0])
                res1 = plans.stream_to_stacked(sj, stream, cand1.scanned,
                                               width)
                return (jax.tree.map(lambda a: a[0], res1), stream.total)
            if spatial:
                spatial_fn = None
                if use_pallas:
                    from repro.kernels.spatial_match import ops as sm_ops
                    spatial_fn = sm_ops.spatial_match
                return (plans.join_spatial(ds, cand, user_locations,
                                           user_brokers, spec.spatial_radius,
                                           spec.payload_bytes, num_brokers,
                                           spatial_fn),
                        jnp.zeros((), jnp.int32))
            return (plans.join_param_targets(
                ds, cand, targets, spec.param_field, spec.payload_bytes,
                num_brokers, up_mask if flags.param_pushdown else None,
                flags.aggregation), jnp.zeros((), jnp.int32))

        fn = jax.jit(run)
        self._cache_put(key, fn)
        return fn, key

    def _cache_put(self, key, fn: Callable, cap: int = 256) -> None:
        """Insert into the plan cache with FIFO eviction — superseded shape
        buckets / flag combos must not pin dead XLA executables forever."""
        if len(self._exec_cache) >= cap:
            self._exec_cache.pop(next(iter(self._exec_cache)))
        self._exec_cache[key] = fn

    def _warm_if_new(self, key, fn: Callable, args: tuple) -> None:
        """Warm (execute + block) a compiled plan ONLY when this (plan key,
        concrete arg shapes) pair has never executed — i.e. on an actual
        trace-cache miss. Timed callers use this so wall time measures
        execution, not tracing; warming unconditionally would run every
        cached executable twice per timed call. Keyed on the plan-cache key
        plus the argument shape/dtype signature (a new shape bucket on a
        cached key still traces, so it still warms)."""
        leaves = jax.tree_util.tree_leaves(args)
        sig = (key, tuple(
            (leaf.shape, str(leaf.dtype)) if hasattr(leaf, "shape")
            else repr(leaf) for leaf in leaves))
        if sig in self._warmed:
            return
        if len(self._warmed) > 1024:   # follows the plan caches' spirit:
            self._warmed.clear()       # never pin unbounded bookkeeping
        self._warmed.add(sig)
        jax.block_until_ready(fn(*args))

    def _delivery_fn(self) -> Callable:
        """The per-channel reference delivery: the SAME fused kernels as
        ``execute_all(deliver=True)`` run on a C==1 stack, so the two paths
        are stats-identical by construction."""
        if self._deliver_jit is None:
            pw, mp = self.deliver_payload_words, self.max_deliver_pairs
            mn, sc = self.max_notify, self.max_spill
            nb = self.brokers.num_brokers
            maint = self.maintenance

            def deliver(res, sids, tb, counts):
                maint.traces += 1
                return deliver_all(res, sids, pw, mp, mn, sc,
                                   target_brokers=tb, num_brokers=nb,
                                   counts=counts)

            self._deliver_jit = jax.jit(deliver)
        return self._deliver_jit

    def _deliver(self, st: ChannelState, result: plans.ChannelResult,
                 aggregated: bool) -> DeliveryStats:
        """Run the broker convert+send stages on one channel's result,
        capture overflow into the spill queue, and account every pair/sID
        (delivered + spilled + dropped == produced, per stage)."""
        res1 = jax.tree.map(lambda a: a[None], result)
        counts = None
        if st.spec.join == "spatial":
            tbl = self._spatial_sids_table(st)
            if tbl is None:
                # spatial targets ARE end-user ids; a 0-wide table selects
                # the brokers' identity fanout (they read targets directly
                # and never index the table's values)
                sids = jnp.zeros((1, 0), dtype=jnp.int32)
                tb = self.user_brokers[None]
            else:
                # cohort channel: targets are cohort SLOTS; the slot->uid
                # table maps them to global user ids, brokers follow the
                # cohort rows
                sids = tbl[None]
                tb = self._channel_users(st)[1][None]
        else:
            sids = self.group_sids_array(st.spec.name, aggregated)[None]
            targets = self._targets(st, aggregated)
            tb = targets.brokers[None]
            # the member-count pass reads the counts the engine maintains
            # instead of re-deriving them from the sID table
            counts = targets.counts[None]
        d = self._delivery_fn()(res1, sids, tb, counts)
        return self._spill_and_stats([st], aggregated, d)[st.spec.name]

    def _spill_and_stats(self, chs: List[ChannelState], layout,
                         d: FusedDelivery,
                         epochs: Optional[List[int]] = None,
                         resolve_tables: Optional[np.ndarray] = None,
                         ranked: Optional[Tuple[np.ndarray, np.ndarray]] = None
                         ) -> Dict[str, DeliveryStats]:
        """Host side of a delivery: push the captured flat spill streams into
        the SpillQueue per channel (entries past the queue's capacity — or
        past the device capture buffer — become counted drops) and assemble
        each channel's conserving DeliveryStats. ``layout`` tags the pair
        lane with the TARGET INDEX SPACE the producing join used (False =
        flat rows, True = compacted group rows, "slot" = aggregator slot
        rows) so the drain re-packs against the matching table.

        ``epochs`` stamps pair entries with the DISPATCH-time epoch instead
        of the live one (a deferred sync may run after churn moved the
        channel on). ``resolve_tables`` (the dispatch-time stacked sID
        tables, host-materialized) switches pair capture to the epoch-free
        RESOLVED lane: each spilled pair's fanout is resolved here, against
        the table its producing call joined, so deferred batched drains
        cannot go stale.

        ``ranked`` (per-channel pruned pair / member-sID counts from the
        enrichment stage) re-enters budget-pruned pairs as counted drops:
        delivery saw the PRUNED result, so its produced counters undershoot
        the report's by exactly these amounts."""
        pack_d = np.asarray(d.pack.delivered)
        pack_p = np.asarray(d.pack.produced)
        fan_d = np.asarray(d.fan.delivered)
        fan_p = np.asarray(d.fan.produced)
        per_broker = np.asarray(d.pack.per_broker)
        pvalid = np.asarray(d.pair_spill.valid)
        prows = np.asarray(d.pair_spill.rows)[pvalid]
        pchan = np.asarray(d.pair_spill.channels)[pvalid]
        ptgts = np.asarray(d.pair_spill.targets)[pvalid]
        svalid = np.asarray(d.sid_spill.valid)
        svals = np.asarray(d.sid_spill.values)[svalid]
        schan = np.asarray(d.sid_spill.channels)[svalid]
        cnt = d.counters
        if cnt is not None:
            retried_p, stale_p, ring_p, retried_s, ring_s = (
                np.asarray(x) for x in cnt)
        out: Dict[str, DeliveryStats] = {}
        for i, st in enumerate(chs):
            name = st.spec.name
            sel = pchan == i
            if resolve_tables is not None:
                rows_i, tgts_i = prows[sel], ptgts[sel]
                sid_rows = resolve_pair_sids(resolve_tables[i], tgts_i)
                spilled_p = self.spill.push_resolved(name, rows_i, tgts_i,
                                                     sid_rows)
            else:
                epoch = st.epoch if epochs is None else epochs[i]
                spilled_p = self.spill.push_pairs(name, layout, prows[sel],
                                                  ptgts[sel], epoch)
            sel = schan == i
            spilled_s = self.spill.push_sids(name, svals[sel])
            ov_p = int(pack_p[i] - pack_d[i])
            ov_s = int(fan_p[i] - fan_d[i])
            rk_p = int(ranked[0][i]) if ranked is not None else 0
            rk_s = int(ranked[1][i]) if ranked is not None else 0
            if cnt is None:
                out[name] = DeliveryStats(
                    delivered_pairs=int(pack_d[i]), spilled_pairs=spilled_p,
                    dropped_pairs=ov_p - spilled_p + rk_p,
                    delivered_sids=int(fan_d[i]), spilled_sids=spilled_s,
                    dropped_sids=ov_s - spilled_s + rk_s,
                    delivered_pairs_broker=tuple(int(x)
                                                 for x in per_broker[i]),
                    ranked_pairs=rk_p, ranked_sids=rk_s)
            else:
                # ring-resident entries count as spilled; overflow past the
                # ring that also missed the queue (or went epoch-stale in
                # the ring) counts as dropped — conservation per stage:
                # delivered + spilled + dropped == produced (fresh + retried)
                host_want_p = ov_p - int(stale_p[i]) - int(ring_p[i])
                host_want_s = ov_s - int(ring_s[i])
                out[name] = DeliveryStats(
                    delivered_pairs=int(pack_d[i]),
                    spilled_pairs=int(ring_p[i]) + spilled_p,
                    dropped_pairs=(int(stale_p[i]) + host_want_p - spilled_p
                                   + rk_p),
                    delivered_sids=int(fan_d[i]),
                    spilled_sids=int(ring_s[i]) + spilled_s,
                    dropped_sids=host_want_s - spilled_s + rk_s,
                    delivered_pairs_broker=tuple(int(x)
                                                 for x in per_broker[i]),
                    retried_pairs=int(retried_p[i]),
                    retried_sids=int(retried_s[i]),
                    ranked_pairs=rk_p, ranked_sids=rk_s)
        return out

    def execute_channel(self, channel: str,
                        flags: plans.ExecutionFlags,
                        advance: bool = True,
                        timed: bool = True,
                        deliver: bool = False,
                        backend: Optional[str] = None) -> ExecutionReport:
        st = self.channels[channel]
        spatial = st.spec.join == "spatial"
        backend = backend or ("pallas" if self.use_pallas else "oracle")
        # The BAD index knows its exact candidate count before execution (the
        # watermark delta) — unlike scans/traditional indexes — so downstream
        # buffers are shape-bucketed to the real volume ("early result
        # filtering" paying off structurally, not just in rows scanned).
        max_cand = None
        if flags.scan_mode == "bad_index":
            pending = int(self.index_state.counts[st.index]
                          - self.index_state.watermarks[st.index])
            bucket = _pow2_bucket(pending, 6)
            max_cand = min(bucket, self.max_candidates)
        targets = self._targets(st, flags.aggregation)
        up_mask = st.user_params.mask()
        args = (self.dataset, self.index_state, targets, up_mask,
                jnp.asarray(st.last_exec_ts, jnp.int32),
                jnp.asarray(st.last_exec_size, jnp.int32),
                *self._channel_users(st))
        if plans.is_compact(backend):
            # per-channel grow-on-overflow, same protocol as the fused path
            key = ("chan", channel, flags, spatial)
            width = (self.max_window if flags.scan_mode == "window"
                     else (max_cand or self.max_candidates))
            stream_cap = min(self._stream_buckets.get(key, 1 << _STREAM_FLOOR),
                             _pow2_bucket(width, _STREAM_FLOOR))
            while True:
                fn, fkey = self._exec_fn(channel, flags, spatial, max_cand,
                                         backend, stream_cap)
                if timed:  # warm so wall time measures execution, not tracing
                    self._warm_if_new(fkey, fn, args)
                t0 = time.perf_counter()
                result, tot = fn(*args)
                jax.block_until_ready(result.num_results)
                wall = time.perf_counter() - t0
                if int(jax.device_get(tot)) <= stream_cap:
                    break
                stream_cap = _pow2_bucket(int(jax.device_get(tot)),
                                          _STREAM_FLOOR)
            self._stream_buckets[key] = stream_cap
        else:
            fn, fkey = self._exec_fn(channel, flags, spatial, max_cand,
                                     backend)
            if timed:  # warm the trace so wall time measures execution
                self._warm_if_new(fkey, fn, args)
            t0 = time.perf_counter()
            result, _tot = fn(*args)
            jax.block_until_ready(result.num_results)
            wall = time.perf_counter() - t0
        if advance:
            self.index_state = bidx.advance_watermark(self.index_state, st.index)
            st.last_exec_ts = self.now
            st.last_exec_size = self.size_host
            st.executions += 1
        overflow = self._deliver(st, result, flags.aggregation) if deliver else None
        return ExecutionReport(
            channel=channel, flags=flags, result=result, wall_time_s=wall,
            num_results=int(result.num_results),
            num_notified=int(result.num_notified),
            scanned=int(result.scanned),
            broker_bytes=np.asarray(result.broker_bytes),
            overflow=overflow)

    # ------------------------------------------------------------------
    # data plane: fused multi-channel execution
    # ------------------------------------------------------------------

    def _stacked_inputs(self, chs: List[ChannelState], aggregated: bool):
        """Device-resident shape-bucketed targets for all param channels —
        see ``_group_state`` for the epoch/delta maintenance contract."""
        c = self._group_state(chs, aggregated)
        return c.targets, c.up_masks, c.domains

    def _stacked_sids(self, chs: List[ChannelState],
                      aggregated: bool) -> jnp.ndarray:
        """Stacked device group-sID tables (C, tmax, cap) for fused
        delivery; rows align with the target slots of the SAME cache entry
        (one patch updates both)."""
        return self._group_state(chs, aggregated).sids

    def _group_state(self, chs: List[ChannelState],
                     aggregated: bool) -> _GroupCache:
        """The fused path's stacked group state, maintained by the
        epoch/delta protocol.

        Shapes are capacity-padded to shared power-of-two buckets (tmax slot
        rows / real max domain / mmax join fan-out), so the fused trace is
        stable across churn; -1 / 0 padding can never form a valid pair. On
        an epoch move the entry is PATCHED in place from the channels' group
        deltas (O(delta) host work + one jitted scatter per changed channel);
        it fully rebuilds only when padded capacity is exceeded, a delta is
        unavailable (log gap / out-of-band mutation), the channel set
        changed, or the engine runs with ``incremental=False`` — the flat
        layout always rebuilds (per-subscription rows have no stable slot
        identity)."""
        names = tuple(st.spec.name for st in chs)
        epochs = [st.epoch for st in chs]
        # keyed by layout AND the group's channel membership: concurrent
        # plan-groups (heterogeneous assignments) each keep their own
        # patchable entry instead of thrashing a single slot
        cache = self._stacked_cache.get(("groups", aggregated, names))
        if cache is not None and cache.names == names:
            if cache.epochs == epochs:
                return cache
            if self.incremental:
                if aggregated:
                    patches = self._group_patches(cache, chs)
                    if patches is not None:
                        self._apply_group_patches(cache, chs, patches)
                        return cache
                else:
                    patches = self._flat_patches(cache, chs)
                    if patches is not None:
                        self._apply_flat_patches(cache, chs, patches)
                        return cache
        cache = self._build_group_state(chs, aggregated)
        self._stacked_put(("groups", aggregated, names), cache)
        return cache

    def _stacked_put(self, key, cache, cap: int = 32) -> None:
        """Insert a stacked cache entry with FIFO eviction — plan switches
        re-group channels, and superseded groupings must not pin dead
        device arrays forever."""
        if key not in self._stacked_cache and len(self._stacked_cache) >= cap:
            self._stacked_cache.pop(next(iter(self._stacked_cache)))
        self._stacked_cache[key] = cache

    def _build_group_state(self, chs: List[ChannelState],
                           aggregated: bool) -> _GroupCache:
        self.maintenance.rebuilds += 1
        names = tuple(st.spec.name for st in chs)
        n = len(chs)
        dmax = max(st.spec.param_domain for st in chs)
        if aggregated and self.incremental:
            # slot-indexed arrays: row == aggregator slot, free slots
            # zero-count — the layout group deltas patch directly
            hosts = [st.aggregator.slot_arrays() for st in chs]
            tmax = _pow2_bucket(max(h[0].shape[0] for h in hosts), 3)
            mmax = _pow2_bucket(
                max(st.aggregator.max_param_fanout() for st in chs), 3)
            cap = max(st.aggregator.cap for st in chs)
            by_param = np.full((n, dmax, mmax), -1, np.int32)
            by_count = np.zeros((n, dmax), np.int32)
            sids = np.full((n, tmax, cap), -1, np.int32)
            for i, (st, h) in enumerate(zip(chs, hosts)):
                for p, row in st.aggregator.param_items():
                    by_param[i, p, :len(row)] = row
                    by_count[i, p] = len(row)
                sids[i, :h[3].shape[0], :h[3].shape[1]] = h[3]
        elif self.incremental:
            # FLAT stable slots: row == per-subscription flat slot, free
            # slots zero-count; join-map rows are positional ((param, pos)
            # cells stable under churn, -1 holes masked by the join) so the
            # churn engine patches this cache cell-wise instead of
            # rebuilding it per epoch
            hosts = [st.aggregator.flat_slot_arrays() for st in chs]
            tmax = _pow2_bucket(max(h[0].shape[0] for h in hosts), 3)
            mmax = _pow2_bucket(
                max(st.aggregator.max_flat_extent() for st in chs), 3)
            cap = 1
            by_param = np.full((n, dmax, mmax), -1, np.int32)
            by_count = np.zeros((n, dmax), np.int32)
            sids = np.full((n, tmax, cap), -1, np.int32)
            for i, (st, h) in enumerate(zip(chs, hosts)):
                for p, row in st.aggregator.flat_param_rows():
                    by_param[i, p, :len(row)] = row
                    by_count[i, p] = len(row)       # extent, holes masked
                sids[i, :h[3].shape[0], 0] = h[3]
        else:
            # compacted build() rows (the pre-churn-engine layout); the flat
            # table IS this with one row per subscription
            hosts2 = [self._targets_host(st, aggregated) for st in chs]
            hosts = [(h[0], h[1], h[2]) for h in hosts2]
            tmax = _pow2_bucket(max(h[0].shape[0] for h in hosts2), 3)
            mmax = _pow2_bucket(max(h[3].shape[1] for h in hosts2), 3)
            by_param = np.full((n, dmax, mmax), -1, np.int32)
            by_count = np.zeros((n, dmax), np.int32)
            srcs = []
            for st in chs:
                if aggregated:
                    groups = st._groups or st.aggregator.build()
                    st._groups = groups
                    srcs.append(np.asarray(groups.group_sids, np.int32))
                else:
                    srcs.append(np.asarray(self._flat_table(st).sids,
                                           np.int32)[:, None])
            cap = max(h.shape[1] for h in srcs)
            sids = np.full((n, tmax, cap), -1, np.int32)
            for i, (h2, h) in enumerate(zip(hosts2, srcs)):
                d, m = h2[3].shape
                by_param[i, :d, :m] = h2[3]
                by_count[i, :d] = h2[4]
                sids[i, :h.shape[0], :h.shape[1]] = h
        params = np.zeros((n, tmax), np.int32)
        brokers = np.zeros((n, tmax), np.int32)
        counts = np.zeros((n, tmax), np.int32)
        up_masks = np.zeros((n, dmax), bool)
        domains = np.zeros((n,), np.int32)
        for i, (st, (p, b, c, *_)) in enumerate(zip(chs, hosts)):
            t = p.shape[0]
            params[i, :t] = p
            brokers[i, :t] = b
            counts[i, :t] = c
            up_masks[i, :st.spec.param_domain] = st.user_params.refcount > 0
            domains[i] = st.spec.param_domain
        targets = plans.TargetArrays(
            jnp.asarray(params), jnp.asarray(brokers), jnp.asarray(counts),
            jnp.asarray(by_param), jnp.asarray(by_count))
        return _GroupCache(names, aggregated, [st.epoch for st in chs],
                           tmax, dmax, mmax, cap, targets,
                           jnp.asarray(up_masks), jnp.asarray(domains),
                           jnp.asarray(sids))

    def _group_patches(self, cache: _GroupCache, chs: List[ChannelState]):
        """Per-channel (slots, params) patch sets covering every epoch since
        the cache's snapshot, or None if any channel must rebuild (delta gap
        or padded capacity exceeded)."""
        out = []
        for st, cached_e in zip(chs, cache.epochs):
            if st.epoch == cached_e:
                out.append(None)
                continue
            if st.epoch - cached_e > len(st.delta_log):
                return None          # gap certain: don't materialize it
            need = set(range(cached_e + 1, st.epoch + 1))
            slots, params_t = set(), set()
            for e, d in st.delta_log:
                if e in need:
                    need.discard(e)
                    if d.full:
                        return None      # whole-table adopt: rebuild
                    slots |= d.slots
                    params_t |= d.params
            agg = st.aggregator
            if need or agg.num_slots > cache.tmax or agg.cap != cache.cap:
                return None
            if any(len(agg.param_slots(p)) > cache.mmax for p in params_t):
                return None
            out.append((slots, params_t))
        return out

    def _apply_group_patches(self, cache: _GroupCache,
                             chs: List[ChannelState], patches) -> None:
        """One jitted scatter per changed channel: touched slot rows and
        touched by-param rows are re-read from the aggregator (current
        content) and written in place. Patch batches are padded to
        power-of-two buckets with out-of-bounds indices (dropped by the
        scatter), so a steady churn rate replays one cached trace."""
        fn = self._group_patch_fn()
        t = cache.targets
        arrays = (t.params, t.brokers, t.counts, t.by_param,
                  t.by_param_count, cache.up_masks, cache.sids)
        for ci, (st, patch) in enumerate(zip(chs, patches)):
            if patch is None:
                continue
            slots, params_t = patch
            # generous bucket floors: small tick-to-tick delta-size jitter
            # stays inside one bucket (one cached trace), scatter cost of
            # the padding is trivial
            kb = _pow2_bucket(len(slots), 7)
            mb = _pow2_bucket(len(params_t), 5)
            sl = np.sort(np.fromiter(slots, np.int64, len(slots)))
            sl_idx = np.full((kb,), cache.tmax, np.int32)   # OOB pad: dropped
            sl_p = np.zeros((kb,), np.int32)
            sl_b = np.zeros((kb,), np.int32)
            sl_c = np.zeros((kb,), np.int32)
            sl_s = np.full((kb, cache.cap), -1, np.int32)
            sl_idx[:len(sl)] = sl
            (sl_p[:len(sl)], sl_b[:len(sl)], sl_c[:len(sl)],
             sl_s[:len(sl)]) = st.aggregator.slot_rows(sl)
            p_idx = np.full((mb,), cache.dmax, np.int32)
            p_rows = np.full((mb, cache.mmax), -1, np.int32)
            p_cnt = np.zeros((mb,), np.int32)
            p_mask = np.zeros((mb,), bool)
            for j, p in enumerate(sorted(params_t)):
                row = st.aggregator.param_slots(p)
                p_idx[j] = p
                p_rows[j, :len(row)] = row
                p_cnt[j] = len(row)
                p_mask[j] = st.user_params.refcount[p] > 0
            arrays = fn(arrays, jnp.asarray(ci, jnp.int32), sl_idx, sl_p,
                        sl_b, sl_c, sl_s, p_idx, p_rows, p_cnt, p_mask)
            self.maintenance.patches += 1
        cache.targets = plans.TargetArrays(*arrays[:5])
        cache.up_masks = arrays[5]
        cache.sids = arrays[6]
        cache.epochs = [st.epoch for st in chs]

    def _group_patch_fn(self) -> Callable:
        if self._patch_groups_jit is None:
            maint = self.maintenance

            def patch(arrays, ci, sl_idx, sl_p, sl_b, sl_c, sl_s,
                      p_idx, p_rows, p_cnt, p_mask):
                maint.traces += 1
                params, brokers, counts, by_param, by_count, up, sids = arrays
                return (params.at[ci, sl_idx].set(sl_p, mode="drop"),
                        brokers.at[ci, sl_idx].set(sl_b, mode="drop"),
                        counts.at[ci, sl_idx].set(sl_c, mode="drop"),
                        by_param.at[ci, p_idx].set(p_rows, mode="drop"),
                        by_count.at[ci, p_idx].set(p_cnt, mode="drop"),
                        up.at[ci, p_idx].set(p_mask, mode="drop"),
                        sids.at[ci, sl_idx].set(sl_s, mode="drop"))

            self._patch_groups_jit = jax.jit(patch)
        return self._patch_groups_jit

    # -- flat-layout stable slots (per-subscription rows) ----------------

    def _flat_patches(self, cache: _GroupCache, chs: List[ChannelState]):
        """Per-channel (flat slots, join-map cells, params) patch sets
        covering every epoch since the cache's snapshot, or None if any
        channel must rebuild (delta gap, whole-table adopt, or padded
        capacity exceeded)."""
        out = []
        for st, cached_e in zip(chs, cache.epochs):
            if st.epoch == cached_e:
                out.append(None)
                continue
            if st.epoch - cached_e > len(st.delta_log):
                return None          # gap certain: don't materialize it
            need = set(range(cached_e + 1, st.epoch + 1))
            slots, cells, params_t = set(), set(), set()
            for e, d in st.delta_log:
                if e in need:
                    need.discard(e)
                    if d.full:
                        return None  # whole-table adopt: rebuild
                    slots |= d.flat_slots
                    cells |= d.flat_cells
                    params_t |= d.params
            agg = st.aggregator
            if need or agg.num_flat_slots > cache.tmax:
                return None
            if any(agg.flat_row_extent(p) > cache.mmax for p in params_t):
                return None
            out.append((slots, cells, params_t))
        return out

    def _apply_flat_patches(self, cache: _GroupCache,
                            chs: List[ChannelState], patches) -> None:
        """One jitted scatter per changed channel: touched flat-slot rows
        are re-read from the aggregator's flat table and touched join-map
        CELLS ((param, position) — stable under churn) are written in
        place, so the patch cost is O(Δ) cells, never O(subs-per-param) row
        rewrites. Batches are padded to power-of-two buckets with
        out-of-bounds indices (dropped by the scatter)."""
        fn = self._flat_patch_fn()
        t = cache.targets
        arrays = (t.params, t.brokers, t.counts, t.by_param,
                  t.by_param_count, cache.up_masks, cache.sids)
        for ci, (st, patch) in enumerate(zip(chs, patches)):
            if patch is None:
                continue
            slots, cells, params_t = patch
            # generous bucket floors (cells run ~2x the slot count: every
            # add/remove touches one slot AND one join-map cell): small
            # tick-to-tick delta-size jitter stays inside one bucket
            kb = _pow2_bucket(len(slots), 7)
            cb = _pow2_bucket(len(cells), 8)
            mb = _pow2_bucket(len(params_t), 5)
            sl = np.sort(np.fromiter(slots, np.int64, len(slots)))
            sl_idx = np.full((kb,), cache.tmax, np.int32)   # OOB pad: dropped
            sl_p = np.zeros((kb,), np.int32)
            sl_b = np.zeros((kb,), np.int32)
            sl_c = np.zeros((kb,), np.int32)
            sl_s = np.full((kb, 1), -1, np.int32)
            sl_idx[:len(sl)] = sl
            p_, b_, c_, s_ = st.aggregator.flat_slot_rows(sl)
            sl_p[:len(sl)], sl_b[:len(sl)], sl_c[:len(sl)] = p_, b_, c_
            sl_s[:len(sl), 0] = s_
            c_p = np.full((cb,), cache.dmax, np.int32)      # OOB pad: dropped
            c_pos = np.zeros((cb,), np.int32)
            c_val = np.full((cb,), -1, np.int32)
            cp, cpos, cval = st.aggregator.flat_cell_rows(sorted(cells))
            c_p[:len(cp)], c_pos[:len(cp)], c_val[:len(cp)] = cp, cpos, cval
            e_idx = np.full((mb,), cache.dmax, np.int32)
            e_cnt = np.zeros((mb,), np.int32)
            e_mask = np.zeros((mb,), bool)
            for j, p in enumerate(sorted(params_t)):
                e_idx[j] = p
                e_cnt[j] = st.aggregator.flat_row_extent(p)
                e_mask[j] = st.user_params.refcount[p] > 0
            arrays = fn(arrays, jnp.asarray(ci, jnp.int32), sl_idx, sl_p,
                        sl_b, sl_c, sl_s, c_p, c_pos, c_val, e_idx, e_cnt,
                        e_mask)
            self.maintenance.patches += 1
        cache.targets = plans.TargetArrays(*arrays[:5])
        cache.up_masks = arrays[5]
        cache.sids = arrays[6]
        cache.epochs = [st.epoch for st in chs]

    def _flat_patch_fn(self) -> Callable:
        if self._patch_flat_jit is None:
            maint = self.maintenance

            def patch(arrays, ci, sl_idx, sl_p, sl_b, sl_c, sl_s,
                      c_p, c_pos, c_val, e_idx, e_cnt, e_mask):
                maint.traces += 1
                params, brokers, counts, by_param, by_count, up, sids = arrays
                return (params.at[ci, sl_idx].set(sl_p, mode="drop"),
                        brokers.at[ci, sl_idx].set(sl_b, mode="drop"),
                        counts.at[ci, sl_idx].set(sl_c, mode="drop"),
                        by_param.at[ci, c_p, c_pos].set(c_val, mode="drop"),
                        by_count.at[ci, e_idx].set(e_cnt, mode="drop"),
                        up.at[ci, e_idx].set(e_mask, mode="drop"),
                        sids.at[ci, sl_idx].set(sl_s, mode="drop"))

            self._patch_flat_jit = jax.jit(patch)
        return self._patch_flat_jit

    # -- stacked spatial user sets (per-channel cohorts) -----------------

    def _stacked_spatial_inputs(self, chs: List[ChannelState]):
        c = self._spatial_state(chs)
        return c.locs, c.brokers

    def _stacked_spatial_sids(self, chs: List[ChannelState]) -> jnp.ndarray:
        """Delivery sID tables for the spatial group: the legacy 0-width
        identity fanout while every channel serves all users (targets ARE
        end-user ids); with cohorts, a (C, ub, 1) slot->uid table so
        delivered sIDs are GLOBAL user ids, not cohort slots."""
        c = self._spatial_state(chs)
        if c.identity:
            return jnp.zeros((len(chs), 0), jnp.int32)
        return c.uids[:, :, None]

    def _spatial_state(self, chs: List[ChannelState]) -> _SpatialCache:
        """Stacked per-channel user sets, maintained by the same epoch/delta
        protocol as the group caches: cohort churn patches slot rows in
        place; a global ``set_user_locations`` (user-version bump), cohort
        creation, capacity overflow, or a delta gap rebuilds."""
        names = tuple(st.spec.name for st in chs)
        cohorted = tuple(st.cohort is not None for st in chs)
        epochs = [st.user_epoch for st in chs]
        cache = self._stacked_cache.get(("spatial", names))
        if cache is not None and cache.names == names \
                and cache.user_version == self._user_version \
                and cache.cohorted == cohorted:
            if cache.epochs == epochs:
                return cache
            if self.incremental:
                patches = self._spatial_patches(cache, chs)
                if patches is not None:
                    self._apply_spatial_patches(cache, chs, patches)
                    return cache
        cache = self._build_spatial_state(chs)
        self._stacked_put(("spatial", names), cache)
        return cache

    def _cohort_rows(self, st: ChannelState, slots=None):
        """Host (locs, brokers, uids) rows for a cohort channel's slots —
        holes (and uids past the current user table) sit at the far sentinel
        / -1 so they can never match or fan out."""
        from repro.kernels.spatial_match.ops import FAR
        uids = st.cohort.slot_uids()
        if slots is not None:
            uids = uids[slots]
        nu = self.user_locations.shape[0]
        ok = (uids >= 0) & (uids < nu)
        safe = np.where(ok, uids, 0)
        locs = np.where(ok[:, None], np.asarray(self.user_locations)[safe],
                        -FAR).astype(np.float32)
        brokers = np.where(ok, np.asarray(self.user_brokers)[safe],
                           0).astype(np.int32)
        return locs, brokers, np.where(ok, uids, -1).astype(np.int32)

    def _build_spatial_state(self, chs: List[ChannelState]) -> _SpatialCache:
        from repro.kernels.spatial_match.ops import FAR
        self.maintenance.rebuilds += 1
        u = self.user_locations.shape[0]
        rows = [u if st.cohort is None else max(st.cohort.num_slots, 1)
                for st in chs]
        ub = _pow2_bucket(max(rows), 3)
        n = len(chs)
        locs = np.full((n, ub, 2), -FAR, np.float32)
        brokers = np.zeros((n, ub), np.int32)
        uids = np.full((n, ub), -1, np.int32)
        for i, st in enumerate(chs):
            if st.cohort is None:
                locs[i, :u] = np.asarray(self.user_locations)
                brokers[i, :u] = np.asarray(self.user_brokers)
                uids[i, :u] = np.arange(u, dtype=np.int32)
            else:
                k = st.cohort.num_slots
                if k:
                    locs[i, :k], brokers[i, :k], uids[i, :k] = \
                        self._cohort_rows(st)
        return _SpatialCache(
            tuple(st.spec.name for st in chs), self._user_version,
            tuple(st.cohort is not None for st in chs),
            [st.user_epoch for st in chs], ub,
            jnp.asarray(locs), jnp.asarray(brokers), jnp.asarray(uids))

    def _spatial_patches(self, cache: _SpatialCache, chs: List[ChannelState]):
        out = []
        for st, cached_e in zip(chs, cache.epochs):
            if st.user_epoch == cached_e:
                out.append(None)
                continue
            if st.user_epoch - cached_e > len(st.user_delta_log):
                return None          # gap certain: don't materialize it
            need = set(range(cached_e + 1, st.user_epoch + 1))
            slots = set()
            for e, touched in st.user_delta_log:
                if e in need:
                    need.discard(e)
                    slots |= touched
            if need or st.cohort is None \
                    or st.cohort.num_slots > cache.ub:
                return None
            out.append(slots)
        return out

    def _apply_spatial_patches(self, cache: _SpatialCache,
                               chs: List[ChannelState], patches) -> None:
        fn = self._spatial_patch_fn()
        arrays = (cache.locs, cache.brokers, cache.uids)
        for ci, (st, slots) in enumerate(zip(chs, patches)):
            if slots is None:
                continue
            kb = _pow2_bucket(len(slots), 7)
            idx = np.full((kb,), cache.ub, np.int32)        # OOB pad: dropped
            sl = np.asarray(sorted(slots), np.int32)
            idx[:len(sl)] = sl
            l_rows = np.zeros((kb, 2), np.float32)
            b_rows = np.zeros((kb,), np.int32)
            u_rows = np.full((kb,), -1, np.int32)
            l, b, uu = self._cohort_rows(st, sl)
            l_rows[:len(sl)] = l
            b_rows[:len(sl)] = b
            u_rows[:len(sl)] = uu
            arrays = fn(arrays, jnp.asarray(ci, jnp.int32), idx,
                        l_rows, b_rows, u_rows)
            self.maintenance.patches += 1
        cache.locs, cache.brokers, cache.uids = arrays
        cache.epochs = [st.user_epoch for st in chs]

    def _spatial_patch_fn(self) -> Callable:
        if self._patch_spatial_jit is None:
            maint = self.maintenance

            def patch(arrays, ci, idx, l_rows, b_rows, u_rows):
                maint.traces += 1
                locs, brokers, uids = arrays
                return (locs.at[ci, idx].set(l_rows, mode="drop"),
                        brokers.at[ci, idx].set(b_rows, mode="drop"),
                        uids.at[ci, idx].set(u_rows, mode="drop"))

            self._patch_spatial_jit = jax.jit(patch)
        return self._patch_spatial_jit

    def _exec_all_fn(self, param_chs: List[ChannelState],
                     spatial_chs: List[ChannelState],
                     plan: plans.ChannelPlan, max_cand: int,
                     deliver: bool = False, p_stream: int = 0,
                     s_stream: int = 0,
                     donate_rings: bool = False) -> Tuple[Callable, tuple]:
        """ONE compiled plan for every channel of a plan-group: stacked
        candidate discovery per join group (param / spatial), vmapped joins,
        fused broker accounting. With a pallas-family backend the discovery
        runs the Pallas ``predicate_filter`` kernel and the spatial join the
        Pallas ``spatial_match`` kernel (both batched over the channel
        axis). The compact backends additionally compress the discovered
        candidates into a channel-major CSR stream (``p_stream`` /
        ``s_stream`` capacities, chosen by ``_run_compact_group``) and run
        the join + accounting over live entries only, scattering back to the
        stacked layout so delivery is bit-identical to the padded path. With
        ``deliver`` the broker convert+send stages (``deliver_all``) run in
        the SAME call — no host round-trip between discovery and fanout.

        The compiled function returns ``(res_p, res_s, del_p, del_s,
        (tot_p, tot_s), (rank_p, rank_s))`` — the totals are the
        pre-truncation live-candidate counts (0 on the padded backends),
        read by the grow loop to detect stream overflow; the rank entries
        are each ``(ranked_pairs, ranked_sids)`` (C,) counters from the
        enrichment stage's budget prune (None when no stage is active).
        When the dispatched plan carries a ``scorer`` tag the engine's
        ``enrichment`` stage scores each join group's candidate slots and
        prunes the lowest-scoring pairs past the budget BEFORE
        ``deliver_all`` — in the same call, so the hook adds no sync; the
        reports still carry the FULL join result (``num_results`` stays the
        produced count; ranked drops land in DeliveryStats). With
        ``donate_rings`` the retry-ring arguments are donated, so at steady
        state the ring buffers update in place (the dispatcher stores the
        OUTPUT ring and never re-presents the input handle; the compact
        grow loop must NOT donate — it re-presents the same ring to the
        re-run). Returns ``(fn, key)``.

        The stages run under named scopes, which only tag the ops'
        metadata for the device trace: ``bad.discover`` (discovery and the
        CSR compaction), ``bad.join`` (the joins), and inside
        ``deliver_all`` ``bad.convert``, ``bad.send`` and ``bad.ring``."""
        key = ("all", plan, max_cand, deliver, p_stream, s_stream,
               donate_rings,
               tuple((st.spec, st.index) for st in param_chs),
               tuple((st.spec, st.index) for st in spatial_chs))
        cached = self._exec_cache.get(key)
        if cached is not None:
            return cached, key
        conds = self._conds
        max_window = self.max_window
        num_brokers = self.brokers.num_brokers
        scan_mode = plan.scan_mode
        pushdown = plan.param_pushdown
        aggregated = plan.aggregation
        use_pallas = plans.backend_family(plan.backend) == "pallas"
        compact = plans.is_compact(plan.backend)
        join_fn = None
        if use_pallas:
            from repro.kernels.predicate_filter import ops as pf_ops
            from repro.kernels.spatial_match import ops as sm_ops
            spatial_fn = sm_ops.spatial_match
            if plan.backend == "compact_pallas":
                from repro.kernels.join_compact import ops as jc_ops
                join_fn = jc_ops.join_pairs
        else:
            spatial_fn = None

        def group_statics(chs):
            rows = [st.index for st in chs]
            conds_sub = CompiledConditions(
                conds.field_idx[rows], conds.op[rows],
                conds.value[rows], conds.npreds[rows])
            best = jnp.asarray(
                [int(np.argmax([_pred_rank(p) for p in st.spec.fixed_preds]))
                 if st.spec.fixed_preds else 0 for st in chs], jnp.int32)
            match_fn = match_rows_fn = None
            if use_pallas:
                match_fn = lambda f, cs=conds_sub: pf_ops.predicate_filter(f, cs)
                match_rows_fn = (
                    lambda f, cs=conds_sub: pf_ops.predicate_filter_rows(f, cs))
            return (conds_sub, best, jnp.asarray(rows, jnp.int32),
                    match_fn, match_rows_fn)

        p_static = group_statics(param_chs) if param_chs else None
        s_static = group_statics(spatial_chs) if spatial_chs else None
        radii = jnp.asarray([st.spec.spatial_radius for st in spatial_chs],
                            jnp.float32)

        def discover(ds, index_state, static, last_ts, last_size):
            conds_sub, best, ch_rows, match_fn, match_rows_fn = static
            if scan_mode == "full":
                return plans.candidates_full_scan_all(ds, conds_sub, last_ts,
                                                      max_cand, match_fn)
            if scan_mode == "window":
                return plans.candidates_window_all(ds, conds_sub, last_size,
                                                   max_window, match_rows_fn)
            if scan_mode == "trad_index":
                return plans.candidates_trad_index_all(
                    ds, conds_sub, best, last_size, max_window, max_cand,
                    match_rows_fn)
            return plans.candidates_bad_index_all(index_state, ch_rows,
                                                  max_cand)

        pw, mp = self.deliver_payload_words, self.max_deliver_pairs
        mn, sc = self.max_notify, self.max_spill
        maint = self.maintenance
        # the enrichment stage binds at trace time, keyed by the plan's
        # scorer tag (stamped by ``dispatch``); a tagged plan on an engine
        # whose stage was detached mid-flight falls back to no-op
        stage = (self.enrichment
                 if deliver and plan.scorer is not None else None)

        def run(ds, index_state, p_in, s_in, p_ring, s_ring):
            maint.traces += 1          # trace-time side effect: counts traces
            res_p = res_s = del_p = del_s = None
            rank_p = rank_s = None
            tot_p = tot_s = jnp.zeros((), jnp.int32)
            if p_static is not None:
                with jax.named_scope("bad.discover"):
                    cand = discover(ds, index_state, p_static,
                                    p_in["last_ts"], p_in["last_size"])
                    if compact:
                        stream = plans.compact_candidates(cand, p_stream)
                        tot_p = stream.total
                with jax.named_scope("bad.join"):
                    if compact:
                        sj = plans.join_param_stream(
                            ds, stream, p_in["targets"], p_in["param_field"],
                            p_in["payload"], num_brokers,
                            p_in["up_masks"] if pushdown else None,
                            aggregated, p_in["domains"], join_fn)
                        res_p = plans.stream_to_stacked(
                            sj, stream, cand.scanned,
                            min(p_stream, cand.rows.shape[1]))
                    else:
                        res_p = plans.join_param_targets_all(
                            ds, cand, p_in["targets"], p_in["param_field"],
                            p_in["payload"], num_brokers,
                            p_in["up_masks"] if pushdown else None,
                            aggregated, p_in["domains"])
                if deliver:
                    res_del = res_p
                    if stage is not None:
                        res_del, rkp, rks = enrich.rank_result(
                            stage, ds, res_p, p_static[2], p_in["sids"],
                            counts=p_in["targets"].counts)
                        rank_p = (rkp, rks)
                    del_p = deliver_all(
                        res_del, p_in["sids"], pw, mp, mn, sc,
                        target_brokers=p_in["targets"].brokers,
                        num_brokers=num_brokers,
                        counts=p_in["targets"].counts,
                        ring=p_ring, epochs=p_in.get("epochs"))
            if s_static is not None:
                with jax.named_scope("bad.discover"):
                    cand = discover(ds, index_state, s_static,
                                    s_in["last_ts"], s_in["last_size"])
                    if compact:
                        stream = plans.compact_candidates(cand, s_stream)
                        tot_s = stream.total
                with jax.named_scope("bad.join"):
                    if compact:
                        sj = plans.join_spatial_stream(
                            ds, stream, s_in["locs"], s_in["brokers"], radii,
                            s_in["payload"], num_brokers)
                        res_s = plans.stream_to_stacked(
                            sj, stream, cand.scanned,
                            min(s_stream, cand.rows.shape[1]))
                    else:
                        res_s = plans.join_spatial_all(
                            ds, cand, s_in["locs"], s_in["brokers"], radii,
                            s_in["payload"], num_brokers, spatial_fn)
                if deliver:
                    res_del = res_s
                    if stage is not None:
                        res_del, rkp, rks = enrich.rank_result(
                            stage, ds, res_s, s_static[2], s_in["sids"])
                        rank_s = (rkp, rks)
                    del_s = deliver_all(
                        res_del, s_in["sids"], pw, mp, mn, sc,
                        target_brokers=s_in["brokers"],
                        num_brokers=num_brokers,
                        ring=s_ring, epochs=s_in.get("epochs"))
            return (res_p, res_s, del_p, del_s, (tot_p, tot_s),
                    (rank_p, rank_s))

        fn = (jax.jit(run, donate_argnums=(4, 5)) if donate_rings
              else jax.jit(run))
        self._cache_put(key, fn)
        return fn, key

    def execute_all(self, flags: Optional[plans.ExecutionFlags] = None,
                    advance: bool = True, timed: bool = True,
                    deliver: bool = False) -> Dict[str, ExecutionReport]:
        """Execute EVERY channel — param-join AND spatial — in one fused
        jitted call per PLAN-GROUP: stacked candidate discovery per join
        group, vmapped param join, vmapped spatial join (per-channel radii
        over the stacked user sets), fused broker accounting. No per-channel
        host round-trips remain on the hot path.

        ``flags=None`` (the planner-driven mode) partitions channels by
        their assigned ``ChannelPlan`` (``set_plan`` / engine default):
        channels sharing a plan run in ONE fused call, heterogeneous
        assignments run one call per distinct plan, each with its own
        stacked caches and retry ring (keyed by the full plan identity).
        Passing explicit ``flags`` forces the legacy homogeneous path —
        every channel runs that plan under the engine backend (assignments
        are ignored, not overwritten), which for a single plan is exactly
        the pre-planner behavior: one fused call for the whole engine.

        Result-for-result equivalent to looping ``execute_channel`` — each
        channel's report carries its own counts/bytes; ``wall_time_s`` is
        its plan-group's fused wall time amortized per channel.
        ``deliver=True`` runs the broker convert+send stages
        (``broker.deliver_all``) INSIDE each group's jitted call — stacked
        wire packing, stacked sID fanout, one-hot per-broker accounting,
        flat spill capture — and surfaces per-channel ``DeliveryStats`` in
        ``report.overflow``, stats-identical to the per-channel ``_deliver``
        path. A plan switch between calls migrates the superseded group's
        ring state through ``_flush_ring`` into the host SpillQueue, so
        delivered + spilled + dropped == produced telescopes across the
        switch.

        Thin wrapper over ``execute(ExecutionRequest(...))`` — the single
        execution surface; equivalent to ``dispatch_all(...).sync()``. The
        pipelined runtime (``core/runtime.py``) calls ``dispatch_all``
        directly and defers the sync one or more ticks.
        """
        return self.execute(plans.ExecutionRequest(
            flags=flags, advance=advance, timed=timed, deliver=deliver))

    def execute(self, request: plans.ExecutionRequest
                ) -> Dict[str, ExecutionReport]:
        """Run one ``ExecutionRequest`` synchronously: ``dispatch(...)``
        then ``sync()`` — the single execution surface every facade
        (``execute_all``, ``dispatch_all``) routes through."""
        return self.dispatch(request).sync()

    def dispatch_all(self, flags: Optional[plans.ExecutionFlags] = None,
                     advance: bool = True, timed: bool = False,
                     deliver: bool = False,
                     resolve_spills: bool = False):
        """``dispatch`` under the legacy keyword surface (``flags`` forces
        one homogeneous plan; None runs the per-channel assignments)."""
        return self.dispatch(plans.ExecutionRequest(
            flags=flags, advance=advance, timed=timed, deliver=deliver,
            resolve_spills=resolve_spills))

    def dispatch(self, request: plans.ExecutionRequest):
        """Dispatch every plan-group's fused call WITHOUT waiting for the
        device: returns a ``runtime.PendingExecution`` whose ``.sync()``
        materializes the per-channel reports (one bulk device->host transfer
        per join group) and runs the host half of delivery accounting
        (SpillQueue pushes, conserving DeliveryStats).

        The request resolves to one plan per requested channel
        (``ExecutionRequest.forced_plan`` — explicit plan/flags/backend
        override — falling back to each channel's assignment), and channels
        sharing a plan run in ONE fused call; a homogeneous resolution
        reduces to a single group, which is exactly the legacy
        ``execute_all(flags)`` behavior. With an ``enrichment`` stage
        attached and ``deliver=True`` every dispatched plan is stamped with
        the stage's identity, so compiled executables, stream buckets, and
        retry rings all key on the scorer.

        Everything control-plane-visible happens AT DISPATCH: successor
        retry rings are stored (device handles, no sync), watermarks
        advance, ``last_exec_*`` snapshots move — so back-to-back dispatches
        pipeline correctly and a deferred ``sync()`` observes exactly the
        state its call was dispatched against.

        ``resolve_spills`` captures overflowed pairs into the SpillQueue's
        epoch-free RESOLVED lane (fanout resolved against the dispatch-time
        sID tables at sync) — required when syncs are deferred across
        control-plane churn, where the live epoch may have moved past the
        dispatch-time one before stats materialize.

        Remaining host sync points, by design: the ``bad_index`` scan mode
        reads watermark deltas to bucket candidate shapes, and the compact
        backends read the live-candidate total for the grow-on-overflow
        protocol (both documented in docs/ARCHITECTURE.md).

        Host spans (``jax.profiler.TraceAnnotation``, free while no trace
        runs): ``bad.dispatch`` around it, and per plan-group
        ``bad.dispatch.group`` with ``bad.dispatch.bucket_read`` (the
        ``bad_index`` bucket read), ``bad.dispatch.args`` (the call's
        arguments and rings) and ``bad.dispatch.launch`` (the call)."""
        with TraceAnnotation("bad.dispatch"):
            return self._dispatch(request)

    def _dispatch(self, request: plans.ExecutionRequest):
        from repro.core.runtime import PendingExecution
        deliver = request.deliver
        ordered = sorted(self.channels.values(), key=lambda s: s.index)
        if request.channels is not None:
            unknown = set(request.channels) - set(self.channels)
            if unknown:
                raise KeyError(f"unknown channels: {sorted(unknown)}")
            want = set(request.channels)
            ordered = [st for st in ordered if st.spec.name in want]
        if not ordered:
            return PendingExecution(self, [])
        forced = request.forced_plan(
            "pallas" if self.use_pallas else "oracle")
        plan_for = {}
        for st in ordered:
            p = forced or (st.plan or self.default_plan())
            if forced is None and request.backend is not None:
                p = dataclasses.replace(p, backend=request.backend)
            plan_for[st.spec.name] = p
        if self.enrichment is not None and deliver:
            tag = self.enrichment.identity
            plan_for = {n: dataclasses.replace(p, scorer=tag)
                        for n, p in plan_for.items()}
        # plan-groups in first-channel order: Dict preserves insertion
        # order, so homogeneous assignments reduce to one group == the
        # legacy single fused call
        groups: Dict[plans.ChannelPlan, Tuple[List, List]] = {}
        for st in ordered:
            g = groups.setdefault(plan_for[st.spec.name], ([], []))
            (g[0] if st.spec.join == "param" else g[1]).append(st)
        # a channel-subset dispatch must not treat the other groups' rings
        # as superseded — only full-engine dispatches prune inactive rings
        use_ring = deliver and self.ring_capacity > 0
        if use_ring and request.channels is None:
            # plan-switch ring migration: a ring keyed by a (kind, plan,
            # membership) no longer executing hands its resident entries to
            # the host SpillQueue — tagged with the layout they were
            # produced under, so the drain re-packs against the matching
            # table — instead of being presented against another plan's
            # tables or silently dropped
            active = set()
            for plan, (pchs, schs) in groups.items():
                if pchs:
                    active.add(("param", plan,
                                tuple(st.spec.name for st in pchs)))
                if schs:
                    active.add(("spatial", plan,
                                tuple(st.spec.name for st in schs)))
            for k in [k for k in self._rings if k not in active]:
                self._flush_ring(*self._rings.pop(k))
        pending = []
        for plan, (param_chs, spatial_chs) in groups.items():
            with TraceAnnotation("bad.dispatch.group"):
                pending.append(self._dispatch_plan_group(
                    plan, param_chs, spatial_chs, request.timed, deliver,
                    use_ring, request.resolve_spills))
        if request.advance:
            # watermark advance is a device-side functional update (no
            # sync); the in-flight calls captured the PRE-advance handle
            self.index_state = bidx.advance_watermarks(
                self.index_state,
                jnp.asarray([st.index for st in ordered], jnp.int32))
            for st in ordered:
                st.last_exec_ts = self.now
                st.last_exec_size = self.size_host
                st.executions += 1
        return PendingExecution(self, pending)

    def _dispatch_plan_group(self, plan: plans.ChannelPlan,
                             param_chs: List[ChannelState],
                             spatial_chs: List[ChannelState],
                             timed: bool, deliver: bool,
                             use_ring: bool,
                             resolve_spills: bool) -> "_PendingGroup":
        """Dispatch ONE plan-group's fused call; reports materialize later
        in ``_materialize_group``."""
        chans = param_chs + spatial_chs
        max_cand = self.max_candidates
        if plan.scan_mode == "bad_index":
            # shared shape bucket: the largest watermark delta across THIS
            # group's channels (two bulk host reads, not 2 device reads per
            # channel)
            with TraceAnnotation("bad.dispatch.bucket_read"):
                counts = np.asarray(self.index_state.counts)
                wms = np.asarray(self.index_state.watermarks)
            pending = max(int(counts[st.index] - wms[st.index])
                          for st in chans)
            bucket = _pow2_bucket(pending, 6)
            max_cand = min(bucket, self.max_candidates)
        # The fused aggregated targets of an incremental engine are SLOT
        # indices (free slots padded) and its flat targets are FLAT-slot
        # indices — not build()'s compacted rows — tag their spills with the
        # matching layout so a drain re-packs against the right table.
        # Non-incremental / spatial spills keep the per-channel layouts.
        if self.incremental:
            p_layout = "slot" if plan.aggregation else "flat_slot"
        else:
            p_layout = plan.aggregation
        with TraceAnnotation("bad.dispatch.args"):
            p_names = tuple(st.spec.name for st in param_chs)
            s_names = tuple(st.spec.name for st in spatial_chs)
            p_in = s_in = p_ring = s_ring = None
            if param_chs:
                targets, up_masks, domains = self._stacked_inputs(
                    param_chs, plan.aggregation)
                p_in = dict(
                    targets=targets, up_masks=up_masks, domains=domains,
                    param_field=jnp.asarray(
                        [st.spec.param_field for st in param_chs], jnp.int32),
                    payload=jnp.asarray(
                        [st.spec.payload_bytes for st in param_chs],
                        jnp.int32),
                    last_ts=jnp.asarray(
                        [st.last_exec_ts for st in param_chs], jnp.int32),
                    last_size=jnp.asarray(
                        [st.last_exec_size for st in param_chs], jnp.int32))
                if deliver:
                    p_in["sids"] = self._stacked_sids(param_chs,
                                                      plan.aggregation)
                    if use_ring:
                        p_ring = self._ring_in(
                            ("param", plan, p_names), p_names, len(param_chs))
                        p_in["epochs"] = jnp.asarray(
                            [st.epoch for st in param_chs], jnp.int32)
            if spatial_chs:
                locs, ubrokers = self._stacked_spatial_inputs(spatial_chs)
                s_in = dict(
                    locs=locs, brokers=ubrokers,
                    payload=jnp.asarray(
                        [st.spec.payload_bytes for st in spatial_chs],
                        jnp.int32),
                    last_ts=jnp.asarray(
                        [st.last_exec_ts for st in spatial_chs], jnp.int32),
                    last_size=jnp.asarray(
                        [st.last_exec_size for st in spatial_chs], jnp.int32))
                if deliver:
                    s_in["sids"] = self._stacked_spatial_sids(spatial_chs)
                    if use_ring:
                        s_ring = self._ring_in(
                            ("spatial", plan, s_names), s_names,
                            len(spatial_chs))
                        s_in["epochs"] = jnp.asarray(
                            [st.epoch for st in spatial_chs], jnp.int32)
            args = (self.dataset, self.index_state, p_in, s_in, p_ring, s_ring)
        with TraceAnnotation("bad.dispatch.launch"):
            t0 = time.perf_counter()
            if plans.is_compact(plan.backend):
                # the grow protocol reads the live total (documented sync
                # point); rings are NOT donated — the loop re-presents them
                res, wall = self._run_compact_group(
                    plan, param_chs, spatial_chs, max_cand, deliver, args,
                    timed)
            else:
                donate = use_ring and (p_ring is not None
                                       or s_ring is not None)
                fn, fkey = self._exec_all_fn(param_chs, spatial_chs, plan,
                                             max_cand, deliver,
                                             donate_rings=donate)
                if timed:
                    # warming would CONSUME the donated rings: hand the warm
                    # call copies, dispatch the real call the originals
                    warm_args = args
                    if donate:
                        cp = lambda r: (None if r is None
                                        else jax.tree.map(jnp.copy, r))
                        warm_args = args[:4] + (cp(p_ring), cp(s_ring))
                    self._warm_if_new(fkey, fn, warm_args)
                    t0 = time.perf_counter()
                res = fn(*args)
                wall = 0.0
                if timed:
                    jax.block_until_ready(res)
                    wall = time.perf_counter() - t0
        del_p, del_s = res[2], res[3]
        if use_ring:
            # persist the successor rings AT DISPATCH (device-resident
            # handles, no sync) so the next dispatch re-delivers their
            # content while this call is still in flight
            if param_chs:
                self._rings[("param", plan, p_names)] = (
                    p_names, p_layout, del_p.ring)
            if spatial_chs:
                self._rings[("spatial", plan, s_names)] = (
                    s_names, plan.aggregation, del_s.ring)
        return _PendingGroup(
            plan=plan, param_chs=param_chs, spatial_chs=spatial_chs,
            res=res, p_layout=p_layout, s_layout=plan.aggregation,
            deliver=deliver, wall=wall, t0=t0,
            p_epochs=[st.epoch for st in param_chs],
            s_epochs=[st.epoch for st in spatial_chs],
            p_sids=(p_in or {}).get("sids") if resolve_spills else None,
            s_sids=(s_in or {}).get("sids") if resolve_spills else None)

    def _materialize_group(self, g: "_PendingGroup",
                           reports: Dict[str, ExecutionReport]) -> None:
        """Host half of one dispatched plan-group: one bulk device->host
        transfer per join group, then per-channel numpy views — the
        per-channel path's int()/slice pattern would cost dozens of device
        round-trips here. Delivery stats arrive the same way: the fused call
        already packed/fanned out every channel, so the host only pushes
        spills and reads (C,)-shaped counters. ``wall_time_s`` is the timed
        fused wall amortized per channel, or (untimed) the
        dispatch-to-materialize latency share.

        Host spans: ``bad.sync.wait`` (the block on the call), then per join
        group ``bad.sync.copy``, ``bad.sync.spill`` and ``bad.sync.report``.
        The report span carries the send stage's ``notify_slots`` (the
        notify buffer's slots, ``C * max_notify``) and ``produced_sids`` as
        arguments."""
        res_p, res_s, del_p, del_s, _tots, ranks = g.res
        rank_p, rank_s = ranks
        wall = g.wall
        if not wall:
            # every output of one executable completes together, so the
            # totals scalars stand in for the whole call — blocking on the
            # full tree would touch the successor ring handle, which the
            # NEXT dispatch may already have consumed (donated)
            with TraceAnnotation("bad.sync.wait"):
                jax.block_until_ready(_tots)
            wall = time.perf_counter() - g.t0
        share = wall / max(len(g.param_chs) + len(g.spatial_chs), 1)
        for chs, res, dlv, layout, epochs, sids, rank in (
                (g.param_chs, res_p, del_p, g.p_layout, g.p_epochs,
                 g.p_sids, rank_p),
                (g.spatial_chs, res_s, del_s, g.s_layout, g.s_epochs,
                 g.s_sids, rank_s)):
            if not chs:
                continue
            with TraceAnnotation("bad.sync.copy"):
                host = jax.tree.map(np.asarray, res)
                pay = noti = None
                if g.deliver and self.debug_delivery_buffers:
                    pay = np.asarray(dlv.pack.payload)
                    noti = np.asarray(dlv.fan.notify)
            stats = {}
            if g.deliver:
                with TraceAnnotation("bad.sync.spill"):
                    stats = self._spill_and_stats(
                        chs, layout, dlv, epochs=epochs,
                        resolve_tables=(None if sids is None
                                        else np.asarray(sids)),
                        ranked=None if rank is None else
                        tuple(np.asarray(x) for x in rank))
            # the send stage's notify slots and produced sIDs (all of it
            # delivered, spilled or dropped, ranked drops aside): its share
            # of useful work, read from the trace
            counters = {} if not g.deliver else dict(
                notify_slots=len(chs) * self.max_notify,
                produced_sids=sum(d.delivered_sids + d.overflow_sids
                                  - d.ranked_sids for d in stats.values()))
            with TraceAnnotation("bad.sync.report", **counters):
                for i, st in enumerate(chs):
                    reports[st.spec.name] = ExecutionReport(
                        channel=st.spec.name, flags=g.plan.flags,
                        plan=g.plan,
                        result=jax.tree.map(lambda a, i=i: a[i], host),
                        wall_time_s=share,
                        num_results=int(host.num_results[i]),
                        num_notified=int(host.num_notified[i]),
                        scanned=int(host.scanned[i]),
                        broker_bytes=host.broker_bytes[i],
                        overflow=stats.get(st.spec.name),
                        payload=None if pay is None else pay[i],
                        notify=None if noti is None else noti[i])

    def _run_compact_group(self, plan: plans.ChannelPlan,
                           param_chs: List[ChannelState],
                           spatial_chs: List[ChannelState],
                           max_cand: int, deliver: bool,
                           args: tuple, timed: bool):
        """Run one compact plan-group under the adaptive stream-capacity
        protocol (see the ``_STREAM_FLOOR`` note): per (kind, plan,
        membership) key, start from the remembered bucket, grow straight to
        the observed live total's power-of-two bucket when the stream
        overflowed (re-running ONCE — discovery is pure, and a truncated
        run's outputs are discarded before any delivery or ring state
        escapes, so re-presenting the same ring is safe), and halve the
        bucket after ``_STREAM_PATIENCE`` consecutive runs at <= half
        occupancy. Returns the final run's 6-tuple and its wall time."""
        width = self.max_window if plan.scan_mode == "window" else max_cand
        floor = 1 << _STREAM_FLOOR
        p_key = ("param", plan, tuple(st.spec.name for st in param_chs))
        s_key = ("spatial", plan, tuple(st.spec.name for st in spatial_chs))
        p_cap = (min(self._stream_buckets.get(p_key, floor),
                     _pow2_bucket(len(param_chs) * width, _STREAM_FLOOR))
                 if param_chs else 0)
        s_cap = (min(self._stream_buckets.get(s_key, floor),
                     _pow2_bucket(len(spatial_chs) * width, _STREAM_FLOOR))
                 if spatial_chs else 0)
        while True:
            fn, fkey = self._exec_all_fn(param_chs, spatial_chs, plan,
                                         max_cand, deliver, p_cap, s_cap)
            if timed:  # warm the trace so wall time measures execution
                self._warm_if_new(fkey, fn, args)
            t0 = time.perf_counter()
            res = fn(*args)
            jax.block_until_ready(res)
            wall = time.perf_counter() - t0
            tot_p, tot_s = (int(x) for x in jax.device_get(res[4]))
            grew = False
            if param_chs and tot_p > p_cap:
                p_cap, grew = _pow2_bucket(tot_p, _STREAM_FLOOR), True
            if spatial_chs and tot_s > s_cap:
                s_cap, grew = _pow2_bucket(tot_s, _STREAM_FLOOR), True
            if not grew:
                break
        for key, cap, tot, live in ((p_key, p_cap, tot_p, bool(param_chs)),
                                    (s_key, s_cap, tot_s,
                                     bool(spatial_chs))):
            if not live:
                continue
            if cap > floor and tot <= cap // 2:
                idle = self._stream_idle.get(key, 0) + 1
                if idle >= _STREAM_PATIENCE:
                    cap, idle = cap // 2, 0
                self._stream_idle[key] = idle
            else:
                self._stream_idle[key] = 0
            self._stream_buckets[key] = cap
        return res, wall

    # ------------------------------------------------------------------
    # device-resident retry rings
    # ------------------------------------------------------------------

    def _ring_in(self, key, names: Tuple[str, ...],
                 num_channels: int) -> RetryRing:
        """The resident ring for one plan-group, or a fresh empty one when
        the group's channel set changed (the old ring's entries are handed
        to the host queue — dropped channels drop at drain time, counted —
        never silently lost). Rings whose (kind, plan, membership) key is no
        longer active are flushed up front by ``execute_all``: a caller that
        switches plans must find the inactive ring's entries in the host
        queue (drainable), not stranded on device or replayed against
        another plan's slot tables."""
        cur = self._rings.get(key)
        if cur is not None:
            if cur[0] == names:
                return cur[2]
            del self._rings[key]
            self._flush_ring(*cur)
        return empty_ring(num_channels, self.ring_capacity)

    def _flush_ring(self, names: Tuple[str, ...], layout,
                    ring: RetryRing) -> None:
        """Push a ring's resident entries into the host SpillQueue (pairs
        keep their recorded epoch as the staleness version). Entries past
        the queue's capacity are lost — counted in ``ring_flush_drops``."""
        pc = np.asarray(ring.pair_count)
        sc = np.asarray(ring.sid_count)
        rows = np.asarray(ring.pair_rows)
        tgts = np.asarray(ring.pair_targets)
        eps = np.asarray(ring.pair_epochs)
        vals = np.asarray(ring.sid_values)
        for i, name in enumerate(names):
            n = int(pc[i])
            if n:
                for e in np.unique(eps[i, :n]).tolist():
                    sel = eps[i, :n] == e
                    acc = self.spill.push_pairs(name, layout,
                                                rows[i, :n][sel],
                                                tgts[i, :n][sel], int(e))
                    self.ring_flush_drops += int(sel.sum()) - acc
            m = int(sc[i])
            if m:
                acc = self.spill.push_sids(name, vals[i, :m])
                self.ring_flush_drops += m - acc

    def flush_rings(self) -> None:
        """Hand every ring's resident entries to the host SpillQueue (for
        drain via ``drain_spilled``) and drop the rings — used on channel-set
        changes and by callers that want a host-visible queue state."""
        rings, self._rings = self._rings, {}
        for names, layout, ring in rings.values():
            self._flush_ring(names, layout, ring)

    def ring_pending_pairs(self) -> int:
        return sum(int(np.asarray(r.pair_count).sum())
                   for _, _, r in self._rings.values())

    def ring_pending_sids(self) -> int:
        return sum(int(np.asarray(r.sid_count).sum())
                   for _, _, r in self._rings.values())

    def fused_sids_table(self, name: str, aggregated: bool) -> jnp.ndarray:
        """The sID table matching the FUSED path's pair-target space for one
        channel: slot tables on an incremental engine (group slots when
        aggregated, flat per-subscription slots otherwise), the compacted
        build tables on a rebuild engine, and the cohort slot->uid table (or
        the 0-width identity fanout) for spatial channels."""
        st = self.channels[name]
        if st.spec.join == "spatial":
            tbl = self._spatial_sids_table(st)
            return jnp.zeros((0,), jnp.int32) if tbl is None else tbl
        if self.incremental and aggregated:
            return jnp.asarray(st.aggregator.slot_arrays()[3])
        if self.incremental:
            return jnp.asarray(st.aggregator.flat_slot_arrays()[3])[:, None]
        return self.group_sids_array(name, aggregated)

    # ------------------------------------------------------------------
    # spill retry
    # ------------------------------------------------------------------

    def _synthetic_result(self, rows: np.ndarray,
                          tgts: np.ndarray) -> plans.ChannelResult:
        """A shape-bucketed ChannelResult holding exactly the given (row,
        target) pairs — the drain path's re-entry into the broker kernels."""
        n = len(rows)
        bucket = _pow2_bucket(n, 6)
        r = np.full((bucket,), -1, np.int32)
        t = np.full((bucket,), -1, np.int32)
        r[:n], t[:n] = rows, tgts
        valid = np.arange(bucket) < n
        z = jnp.zeros((), jnp.int32)
        nb = self.brokers.num_brokers
        return plans.ChannelResult(
            jnp.asarray(r)[:, None], jnp.asarray(t)[:, None],
            jnp.asarray(valid)[:, None], jnp.asarray(r), jnp.asarray(valid),
            z, z, z, jnp.zeros((nb,), jnp.int32), jnp.zeros((nb,), jnp.int32))

    def drain_spilled(self) -> Dict[str, DrainReport]:
        """Re-deliver spilled notifications, exactly once per stage.

        Pairs lane: pop up to ``max_deliver_pairs`` for ONE (channel, layout)
        lane per channel per round (layouts re-pack against different tables
        with different wire widths, so a round's ``DrainReport.payload`` is
        always one coherent buffer; a channel spilled under both layouts
        drains the other lane next round) and re-run the convert stage
        against the channel's CURRENT table of that layout; entries whose
        channel version moved (or whose channel was dropped) are unroutable
        and counted as dropped. Sids lane: pop up to ``max_notify`` per
        channel and re-run the send stage (raw sIDs never go stale).
        Anything that misses this round's buffers is requeued at the front —
        never duplicated, never lost. Call once per tick until
        ``spill.pending_pairs() + spill.pending_sids() == 0``.
        """
        out: Dict[str, DrainReport] = {}

        def merge(name: str, rep: DrainReport) -> None:
            prev = out.get(name)
            if prev is None:
                out[name] = rep
            else:
                out[name] = DrainReport(
                    prev.stats.merged(rep.stats),
                    rep.payload if prev.payload is None else prev.payload,
                    rep.notify if prev.notify is None else prev.notify)

        drained_pairs = set()
        # resolved lane first: epoch-free entries (fanout captured against
        # the producing call's own table) re-enter the convert stage with
        # their recorded sID rows as the table — immune to churn between
        # spill and drain, which is exactly why the pipelined runtime's
        # deferred syncs capture into this lane. Shares the one-pair-lane-
        # per-channel-per-round rule so the payload stays one coherent
        # buffer.
        for name in self.spill.resolved_keys():
            if name in drained_pairs:
                continue
            drained_pairs.add(name)
            rows, tgts, sid_rows = self.spill.pop_resolved(
                name, self.max_deliver_pairs)
            dropped = 0
            payload = None
            delivered = respilled = 0
            if name not in self.channels:
                dropped = len(rows)
            elif len(rows):
                n = len(rows)
                # synthetic targets index the recorded sID rows directly;
                # the wire header's target word is patched back to the true
                # targets after packing
                res = self._synthetic_result(rows,
                                             np.arange(n, dtype=np.int32))
                tbl = np.full((_pow2_bucket(n, 6), sid_rows.shape[1]), -1,
                              np.int32)
                tbl[:n] = sid_rows
                buf, dlv, _ = pack_payloads(res, jnp.asarray(tbl),
                                            self.deliver_payload_words,
                                            self.max_deliver_pairs)
                delivered = int(dlv)
                payload = np.array(buf)   # writable host copy
                payload[:delivered, 1] = tgts[:delivered]
                if delivered < n:   # exact in-order prefix delivered
                    self.spill._push_front_resolved(
                        name, rows[delivered:], tgts[delivered:],
                        sid_rows[delivered:])
                    respilled = n - delivered
            if delivered or dropped or respilled:
                merge(name, DrainReport(
                    DeliveryStats(delivered, respilled, dropped, 0, 0, 0),
                    payload=payload))

        for name, layout in self.spill.pair_keys():
            if name in drained_pairs:
                # one pair lane per channel per round: a channel spilled
                # under BOTH layouts re-packs against different tables with
                # different wire widths — its other lane drains next round,
                # so DrainReport.payload is always a single coherent buffer
                continue
            drained_pairs.add(name)
            st = self.channels.get(name)
            version = st.epoch if st is not None else None
            rows, tgts, stale = self.spill.pop_pairs(
                name, layout, self.max_deliver_pairs, version)
            dropped = stale
            payload = None
            delivered = respilled = 0
            if st is None:
                dropped += len(rows)
            elif len(rows):
                res = self._synthetic_result(rows, tgts)
                if st.spec.join == "spatial":
                    tbl = self._spatial_sids_table(st)
                    sids = jnp.zeros((0,), dtype=jnp.int32) \
                        if tbl is None else tbl
                elif layout == "slot":
                    # fused incremental-aggregated spills target SLOT rows
                    sids = jnp.asarray(st.aggregator.slot_arrays()[3])
                elif layout == "flat_slot":
                    # fused incremental-flat spills target FLAT slot rows
                    sids = jnp.asarray(
                        st.aggregator.flat_slot_arrays()[3])[:, None]
                else:
                    sids = self.group_sids_array(name, layout)
                buf, dlv, _ = pack_payloads(res, sids,
                                            self.deliver_payload_words,
                                            self.max_deliver_pairs)
                delivered = int(dlv)
                payload = np.asarray(buf)
                if delivered < len(rows):   # exact in-order prefix delivered
                    self.spill._push_front_pairs(
                        name, layout, rows[delivered:], tgts[delivered:],
                        st.epoch)
                    respilled = len(rows) - delivered
            if delivered or dropped or respilled:
                merge(name, DrainReport(
                    DeliveryStats(delivered, respilled, dropped, 0, 0, 0),
                    payload=payload))

        for name in self.spill.sid_keys():
            sids = self.spill.pop_sids(name, self.max_notify)
            if not len(sids):
                continue
            # identity fanout: targets ARE the sIDs, so the send stage
            # re-emits them verbatim in spill order
            res = self._synthetic_result(sids, sids)
            buf, dlv, _ = fanout_sids(res, jnp.zeros((0,), jnp.int32),
                                      self.max_notify)
            delivered = int(dlv)
            respilled = len(sids) - delivered
            if respilled:
                self.spill._push_front_sids(name, sids[delivered:])
            merge(name, DrainReport(
                DeliveryStats(0, 0, 0, delivered, respilled, 0),
                notify=np.asarray(buf)))
        return out


def _pow2_bucket(n: int, floor_bits: int) -> int:
    """Smallest power of two >= n, clamped below by 2**floor_bits. Shared by
    every shape-bucketing site so fused and per-channel traces agree."""
    return 1 << max(floor_bits, (max(n, 1) - 1).bit_length())


# Compacted-stream capacity policy: streams start at 2**_STREAM_FLOOR
# entries, grow straight to the power-of-two bucket of the observed live
# total on overflow (ONE re-run — the truncated run's outputs are discarded,
# never delivered, so re-presenting the same ring to the re-run is safe),
# and halve after _STREAM_PATIENCE consecutive runs at <= half occupancy.
# Buckets converge to the workload's live-candidate envelope, after which
# the (plan, bucket) cache key is stable: zero retraces at steady state.
_STREAM_FLOOR = 7
_STREAM_PATIENCE = 8


def _pred_rank(p) -> int:
    """Heuristic selectivity rank for picking the traditional-index field."""
    from repro.core.predicates import EQ
    return 2 if p.op == EQ else 1


# jit-compiled shared helpers (module-level so lru caches are shared)
_append = R.append
_insert = bidx.insert
