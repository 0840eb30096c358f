"""Broker subsystem (paper §3.2, §4.1.2, Table 2).

Brokers are HTTP endpoints in the real platform; here they are simulated but
their *work* is real and measurable, mirroring Table 2's three stages:

  receive  -- proportional to platform->broker bytes (ChannelResult.broker_bytes)
  convert  -- "converting to JSON": materialize a wire payload buffer. For the
              original layout that is one record copy per subscription; for the
              aggregated layout one record copy per group + the sID list.
  send     -- per-subscriber dispatch; identical between layouts (Table 2).

Two delivery paths share the same single-channel kernels:

  per-channel -- ``pack_payloads`` / ``fanout_sids``: one channel's result,
                 one host call each (the Table 2 reference path).
  fused       -- ``pack_payloads_all`` / ``fanout_sids_all`` / ``deliver_all``:
                 every channel's convert+send in ONE jitted computation over
                 the stacked channel axis, with per-channel caps and one-hot
                 per-broker accounting, so delivery runs inside the SAME
                 device program as execution. The send stage marks each
                 pair's run start in the notify buffer and fills the runs by
                 a prefix max; the convert stage and the overflow tails
                 binary-search their source pair in per-channel prefix sums.
                 The work is proportional to the delivery capacity + total
                 overflow + the C x max-pending pair grid, not to the
                 C x max-pending x member-cap member grid. Overflowed
                 pairs/sIDs land in the device-resident ``RetryRing`` (when
                 the caller passes one — re-packed and re-delivered ahead of
                 the fresh result on the NEXT call, epoch-masked staleness)
                 and past its window in compacted flat channel-major spill
                 streams for the engine's host-side SpillQueue.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import plans
from repro.core.plans import ChannelResult

HEADER_WORDS = 4  # [row_id, target_idx, member_count, payload_words]


@dataclasses.dataclass
class BrokerRegistry:
    names: Dict[str, int]

    @staticmethod
    def create(*names: str) -> "BrokerRegistry":
        return BrokerRegistry({n: i for i, n in enumerate(names)})

    @property
    def num_brokers(self) -> int:
        return len(self.names)


@dataclasses.dataclass(frozen=True)
class DeliveryStats:
    """Broker delivery accounting for one executed channel (opt-in via
    ``deliver=True``): result pairs packed by the convert stage and end
    subscribers fanned out by the send stage, vs captured into the spill
    queue vs dropped outright (spill buffers full).

    Conservation, per stage: delivered + spilled + dropped == produced.
    ``overflow_*`` keeps the pre-spill-queue view (everything that missed the
    delivery buffer, recoverable or not)."""

    delivered_pairs: int
    spilled_pairs: int
    dropped_pairs: int
    delivered_sids: int
    spilled_sids: int
    dropped_sids: int
    # convert-stage delivered pairs per broker (one-hot accounting); () when
    # the caller supplied no broker table
    delivered_pairs_broker: Tuple[int, ...] = ()
    # retry-ring entries RE-presented this call (they were counted as
    # spilled by an earlier call): produced == fresh + retried, so
    # delivered + spilled + dropped == produced still holds per call and
    # telescopes across ticks (ring-resident entries count as spilled)
    retried_pairs: int = 0
    retried_sids: int = 0
    # pairs (and their member sIDs) the enrichment stage's budget rank
    # dropped BEFORE the convert stage ran (core/enrich.py): the
    # lowest-scoring pairs past the per-channel budget. These are a subset
    # of dropped_* — ranked drops are intentional filtering, never
    # recoverable through the ring/queue — so the per-stage conservation
    # identity above is unchanged
    ranked_pairs: int = 0
    ranked_sids: int = 0

    @property
    def overflow_pairs(self) -> int:
        return self.spilled_pairs + self.dropped_pairs

    @property
    def overflow_sids(self) -> int:
        return self.spilled_sids + self.dropped_sids

    @property
    def overflow(self) -> int:
        return self.overflow_pairs + self.overflow_sids

    @property
    def produced_pairs(self) -> int:
        return self.delivered_pairs + self.overflow_pairs

    @property
    def produced_sids(self) -> int:
        return self.delivered_sids + self.overflow_sids

    def merged(self, other: "DeliveryStats") -> "DeliveryStats":
        return DeliveryStats(
            self.delivered_pairs + other.delivered_pairs,
            self.spilled_pairs + other.spilled_pairs,
            self.dropped_pairs + other.dropped_pairs,
            self.delivered_sids + other.delivered_sids,
            self.spilled_sids + other.spilled_sids,
            self.dropped_sids + other.dropped_sids,
            self.delivered_pairs_broker or other.delivered_pairs_broker,
            self.retried_pairs + other.retried_pairs,
            self.retried_sids + other.retried_sids,
            self.ranked_pairs + other.ranked_pairs,
            self.ranked_sids + other.ranked_sids)


# ---------------------------------------------------------------------------
# single-channel kernels (shared by the per-channel API and the vmapped path)
# ---------------------------------------------------------------------------


def _pack_one(result: ChannelResult, group_sids: jnp.ndarray,
              payload_words: int, max_pairs: int, cap):
    """Convert stage for ONE channel: compact the valid pairs, in ravel order,
    into a (max_pairs, HEADER + sid_cap + payload_words) wire buffer.

    ``cap`` (traced scalar, clamped to ``max_pairs``) is the per-channel
    delivery cap: valid pairs past it are never written — they surface in the
    returned ``spill_mask`` (flat ravel order) for spill capture. Returns
    (buffer, delivered, produced, spill_mask, delivered_mask)."""
    cap_eff = jnp.minimum(jnp.asarray(cap, jnp.int32), max_pairs)
    sid_cap = group_sids.shape[1] if group_sids.ndim == 2 else 1
    rows = result.pair_rows.ravel()
    tgts = result.pair_targets.ravel()
    valid = result.pair_valid.ravel()
    pos = jnp.cumsum(valid.astype(jnp.int32)) - 1
    within = pos < cap_eff
    dest = jnp.where(valid & within, pos, max_pairs)
    width = HEADER_WORDS + sid_cap + payload_words
    out = jnp.zeros((max_pairs + 1, width), dtype=jnp.int32)
    tgt_safe = jnp.maximum(tgts, 0)
    sids = group_sids[tgt_safe] if group_sids.ndim == 2 else tgt_safe[:, None]
    members = jnp.sum((sids >= 0).astype(jnp.int32), axis=-1)
    header = jnp.stack([rows, tgts, members,
                        jnp.full_like(rows, payload_words)], axis=-1)
    payload = jnp.broadcast_to(rows[:, None], (rows.shape[0], payload_words))
    line = jnp.concatenate([header, sids, payload], axis=-1)
    out = out.at[dest].set(jnp.where(valid[:, None], line, 0), mode="drop")
    produced = jnp.sum(valid.astype(jnp.int32))
    delivered = jnp.minimum(produced, cap_eff)
    return out[:max_pairs], delivered, produced, valid & ~within, valid & within


def _fanout_one(result: ChannelResult, group_sids: jnp.ndarray,
                max_notify: int, cap):
    """Send stage for ONE channel: the flat in-order list of end subscribers.
    Returns (buffer, delivered, produced, member_sids, spill_mask) where
    ``member_sids`` is the full flat member stream (-1 where invalid) and
    ``spill_mask`` flags members past the per-channel cap."""
    cap_eff = jnp.minimum(jnp.asarray(cap, jnp.int32), max_notify)
    tgts = result.pair_targets.ravel()
    valid = result.pair_valid.ravel()
    tgt_safe = jnp.maximum(tgts, 0)
    sids = group_sids[tgt_safe] if group_sids.ndim == 2 else tgt_safe[:, None]
    member_valid = (sids >= 0) & valid[:, None]
    flat = jnp.where(member_valid, sids, -1).ravel()
    mask = flat >= 0
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
    within = pos < cap_eff
    dest = jnp.where(mask & within, pos, max_notify)
    out = jnp.full((max_notify + 1,), -1, dtype=jnp.int32)
    out = out.at[dest].set(flat, mode="drop")
    produced = jnp.sum(mask.astype(jnp.int32))
    delivered = jnp.minimum(produced, cap_eff)
    return out[:max_notify], delivered, produced, flat, mask & ~within


def payload_notifications(payload: np.ndarray, delivered: int,
                          payload_words: int) -> np.ndarray:
    """Expand a delivered wire buffer into its (row_id, sID) notification
    pairs — the partition-INDEPENDENT view of the convert stage.

    Group chopping depends on load order (and, on the sharded engine, on
    which shard owns each subscription), so delivered (row, group) pair
    counts differ between equivalent engines; the end-subscriber
    notifications each line fans out to do not. Each delivered line
    contributes one (row_id, sid) per live member sID (the -1 padding in
    the line's sID slots is skipped). Used by the sharded parity harness to
    compare engines whose group partitions differ."""
    buf = np.asarray(payload)[:int(delivered)]
    if buf.size == 0:
        return np.zeros((0, 2), np.int64)
    sid_cap = buf.shape[1] - HEADER_WORDS - payload_words
    sids = buf[:, HEADER_WORDS:HEADER_WORDS + sid_cap].astype(np.int64)
    rows = np.broadcast_to(buf[:, :1].astype(np.int64), sids.shape)
    live = sids >= 0
    return np.stack([rows[live], sids[live]], axis=1)


def resolve_pair_sids(table: np.ndarray, targets: np.ndarray) -> np.ndarray:
    """Resolve spilled pair TARGETS to their member sID rows against the
    producing call's own sID table (host side, numpy).

    This is the capture half of the SpillQueue's epoch-free resolved lane:
    the pipelined runtime materializes delivery stats ticks after dispatch,
    when control-plane churn may have moved the live table past the one the
    join actually used — resolving here, against the DISPATCH-time table,
    makes the spilled entry self-contained, so a deferred drain re-delivers
    the identical notification multiset as an immediate one.

    ``table`` is one channel's slice of the stacked delivery sID table:
    (tmax, cap) group tables resolve by row; the identity fanouts (0-width
    spatial / 1-wide flat) resolve to the target itself — mirroring
    ``_pack_one``'s ndim dispatch. Returns (n, w>=1) int32 rows, -1-padded."""
    targets = np.asarray(targets, np.int32)
    table = np.asarray(table)
    if table.ndim != 2 or table.shape[1] == 0:
        return targets[:, None].copy()
    if table.shape[0] == 0:
        return np.full((len(targets), 1), -1, np.int32)
    safe = np.clip(targets, 0, table.shape[0] - 1)
    return table[safe].astype(np.int32)


def pack_payloads(result: ChannelResult, group_sids: jnp.ndarray,
                  payload_words: int, max_pairs: int
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Materialize the wire payload: (max_pairs, HEADER + cap + payload_words).

    One row per *result pair* (group or subscription). This is the broker's
    "convert" work: in the aggregated layout there are far fewer rows, each
    carrying its sID list; in the original layout there is one row per
    subscription with cap == 1.

    Returns (buffer, delivered, overflow): pairs beyond ``max_pairs`` are
    dropped — never scattered over the last slot — and counted in overflow.
    """
    out, delivered, produced, _, _ = _pack_one(result, group_sids,
                                               payload_words, max_pairs,
                                               max_pairs)
    return out, delivered, produced - delivered


def fanout_sids(result: ChannelResult, group_sids: jnp.ndarray,
                max_notify: int) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The broker's "send" stage: the flat list of end subscribers to notify.
    Identical volume for original and aggregated layouts (Table 2, row 3).

    Returns (buffer, delivered, overflow) — overflow counts sIDs dropped
    because the notify buffer was full."""
    out, delivered, produced, _, _ = _fanout_one(result, group_sids,
                                                 max_notify, max_notify)
    return out, delivered, produced - delivered


# ---------------------------------------------------------------------------
# fused multi-channel delivery: one jitted call covers every channel's
# convert+send, so execution and delivery share a single device program.
#
# Formulation: the work is proportional to the DELIVERY CAPACITY
# (C x (max_pairs + max_notify) + spill) plus O(C x P) passes over the pair
# grid — never to the C x P x member-cap member grid the stacked results
# would expand to.
#   send (the whole notify buffer): each non-empty pair SCATTERS its index
#     at the slot its run of members starts at — one scatter over the
#     C x P pair grid, of the order of the member counts and prefix sums
#     the stage already takes — and a prefix max along the slots fills each
#     run with its owner (``_dense_ranks``). The scatter never runs over
#     the member grid.
#   convert lines, ring tails, spill slots (short sets of ranks): each
#     output slot binary-searches its source pair in the per-channel prefix
#     sums and gathers it.
# ---------------------------------------------------------------------------


class PackedDelivery(NamedTuple):
    """Stacked convert-stage output (leading channel axis C)."""

    payload: jnp.ndarray     # (C, max_pairs, width) int32 wire buffers
    delivered: jnp.ndarray   # (C,) int32 pairs written
    produced: jnp.ndarray    # (C,) int32 valid pairs (pre-cap)
    spill_mask: jnp.ndarray  # (C, Rm*maxT) bool: valid pairs past the cap
    per_broker: jnp.ndarray  # (C, B) int32 delivered pairs per broker


class FanoutDelivery(NamedTuple):
    """Stacked send-stage output (leading channel axis C)."""

    notify: jnp.ndarray       # (C, max_notify) int32 flat sID dispatch
    delivered: jnp.ndarray    # (C,) int32 sIDs written
    produced: jnp.ndarray     # (C,) int32 member sIDs (pre-cap)


class RetryRing(NamedTuple):
    """Device-resident retry state for fused delivery: per-channel windows
    (C, W) of overflowed pairs — with the subscription EPOCH each indexes,
    for staleness masking — and overflowed sIDs (never stale). Entries are
    stored as compacted prefixes (``*_count`` gives each channel's live
    prefix). The ring is an INPUT and an OUTPUT of ``deliver_all``: resident
    entries are re-packed and re-delivered ahead of the fresh result inside
    the next call, so sustained overflow never round-trips through the
    host."""

    pair_rows: jnp.ndarray      # (C, W) int32
    pair_targets: jnp.ndarray   # (C, W) int32
    pair_epochs: jnp.ndarray    # (C, W) int32
    pair_count: jnp.ndarray     # (C,) int32
    sid_values: jnp.ndarray     # (C, W) int32
    sid_count: jnp.ndarray      # (C,) int32

    @property
    def window(self) -> int:
        return self.pair_rows.shape[1]


def empty_ring(num_channels: int, window: int) -> RetryRing:
    # one buffer PER field: the engine donates rings into the fused call,
    # and XLA rejects donating the same buffer twice in one execute
    def neg():
        return jnp.full((num_channels, window), -1, jnp.int32)

    def z1():
        return jnp.zeros((num_channels,), jnp.int32)

    return RetryRing(neg(), neg(), jnp.zeros((num_channels, window),
                                             jnp.int32),
                     z1(), neg(), z1())


class RingCounters(NamedTuple):
    """Per-channel (C,) ring accounting of one ring-aware delivery call."""

    retried_pairs: jnp.ndarray   # ring pair entries re-presented (incl stale)
    stale_pairs: jnp.ndarray     # of those, dropped for an epoch mismatch
    ring_pairs: jnp.ndarray      # pairs resident in the OUTPUT ring
    retried_sids: jnp.ndarray    # ring sid entries re-presented
    ring_sids: jnp.ndarray       # sids resident in the OUTPUT ring


class FusedDelivery(NamedTuple):
    """Both stages plus the compacted flat spill streams (channel identity
    preserved) for the engine's SpillQueue. Ring-aware calls additionally
    carry the successor ``ring`` and its ``counters``; the spill streams
    then hold only what overflowed PAST the ring (the host queue as the
    ring's bounded last resort).

    LAZY-STATS CONTRACT: every field is a device-array handle valid the
    moment the producing jitted call RETURNS (dispatch), not when it
    completes — holding one costs nothing and forces no sync. The engine's
    pipelined runtime threads ``ring`` straight into the next dispatch and
    defers every host read (``np.asarray`` of the stats/spill/payload
    fields) to ``PendingExecution.sync()``, ticks later."""

    pack: PackedDelivery
    fan: FanoutDelivery
    pair_spill: plans.PairStream   # overflowed (row, channel, target) pairs
    sid_spill: plans.ValueStream   # overflowed (sid, channel) end subscribers
    ring: Optional[RetryRing] = None
    counters: Optional[RingCounters] = None


def _pair_layout(result: ChannelResult, caps, cap_limit: int):
    """Shared per-channel pair bookkeeping for the stacked delivery stages:
    (valid2, rows2, tgt2, cumv, produced, cap), all (C, P)-shaped. ``cumv``
    is the inclusive per-channel prefix count of valid pairs (ravel order) —
    slot q's source pair is ``searchsorted(cumv[c], q, 'right')``."""
    C = result.pair_valid.shape[0]
    valid2 = result.pair_valid.reshape(C, -1)
    rows2 = result.pair_rows.reshape(C, -1)
    tgt2 = result.pair_targets.reshape(C, -1)
    cumv = jnp.cumsum(valid2.astype(jnp.int32), axis=1)
    produced = cumv[:, -1]
    if caps is None:
        cap = jnp.full((C,), cap_limit, dtype=jnp.int32)
    else:
        cap = jnp.minimum(jnp.asarray(caps, jnp.int32), cap_limit)
    return valid2, rows2, tgt2, cumv, produced, cap


def _member_counts(group_sids: jnp.ndarray, valid2: jnp.ndarray,
                   tgt2: jnp.ndarray,
                   counts: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """(C, P) member count per pair. With ``counts`` (C, T) — the
    ``TargetArrays.counts`` the engine already maintains — the pass is ONE
    O(C*P) gather, fully capacity-proportional. Without it the table is
    re-derived by an O(C*T*cap) reduction over ``group_sids`` (the
    standalone-kernel fallback); either way never O(C*P*cap) per-pair
    reductions. Requires group rows to pack members as a -1-padded PREFIX
    (the layout every table builder in subscriptions.py produces, and what
    the maintained counts equal by construction)."""
    if group_sids.shape[-1] == 0:       # identity fanout: 1 member per pair
        return jnp.where(valid2 & (tgt2 >= 0), 1, 0).astype(jnp.int32)
    if counts is None:
        counts = jnp.sum((group_sids >= 0).astype(jnp.int32), axis=-1)
    ch = jnp.arange(valid2.shape[0], dtype=jnp.int32)[:, None]
    return jnp.where(valid2, counts[ch, jnp.maximum(tgt2, 0)], 0)


def _pack_lines(rows: jnp.ndarray, tgts: jnp.ndarray, ok: jnp.ndarray,
                ch: jnp.ndarray, group_sids: jnp.ndarray, counts,
                payload_words: int, target_brokers,
                num_brokers: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Assemble the convert-stage wire lines + one-hot per-broker accounting
    for already-resolved (C, Q) output slots (``rows``/``tgts`` masked to 0
    where not ``ok``) — the single definition of the wire format, shared by
    the plain and ring-aware fused convert stages."""
    tgt_safe = jnp.where(ok, jnp.maximum(tgts, 0), 0)
    if group_sids.shape[-1] == 0:       # identity fanout
        members = jnp.where(ok, 1, 0)
        sids = tgt_safe[..., None]
    else:
        m_table = (counts if counts is not None else
                   jnp.sum((group_sids >= 0).astype(jnp.int32), axis=-1))
        members = jnp.where(ok, m_table[ch, tgt_safe], 0)
        sids = group_sids[ch, tgt_safe]
    header = jnp.stack([rows, tgts, members,
                        jnp.where(ok, payload_words, 0)], axis=-1)
    payload = jnp.broadcast_to(rows[..., None],
                               rows.shape + (payload_words,))
    line = jnp.concatenate([header, jnp.where(ok[..., None], sids, 0),
                            payload], axis=-1)
    if target_brokers is None or num_brokers == 0:
        per_broker = jnp.zeros((rows.shape[0], 0), dtype=jnp.int32)
    else:
        bids = jnp.where(ok, target_brokers[ch, tgt_safe], num_brokers)
        one_hot = bids[..., None] == jnp.arange(num_brokers, dtype=jnp.int32)
        per_broker = jnp.sum(one_hot.astype(jnp.int32), axis=1)
    return jnp.where(ok[..., None], line, 0), per_broker


def _source_pair(cum: jnp.ndarray, q: jnp.ndarray) -> jnp.ndarray:
    """Per-channel binary search: source index for each output rank. ``cum``
    (C, P) inclusive prefix counts, ``q`` (C, Q) target ranks -> (C, Q)."""
    return jax.vmap(lambda c, k: jnp.searchsorted(c, k, side="right"))(cum, q)


def _gather(arr2: jnp.ndarray, p: jnp.ndarray) -> jnp.ndarray:
    return jnp.take_along_axis(arr2, p, axis=1)


def pack_payloads_all(result: ChannelResult, group_sids: jnp.ndarray,
                      payload_words: int, max_pairs: int,
                      caps: Optional[jnp.ndarray] = None,
                      target_brokers: Optional[jnp.ndarray] = None,
                      num_brokers: int = 0,
                      counts: Optional[jnp.ndarray] = None
                      ) -> PackedDelivery:
    """Convert stage for EVERY channel at once. ``result`` leaves carry a
    leading C axis (the fused join output); ``group_sids`` is (C, T, cap) for
    group/flat tables or (C, 0) to select the identity fanout (spatial
    channels). Each channel's delivered prefix is bit-identical to
    ``pack_payloads`` on its slice.

    ``caps`` (C,) bounds delivery per channel (default: the shared buffer
    size). ``target_brokers`` (C, T) — broker id by target index — enables
    one-hot per-broker accounting of *delivered* pairs, returned as
    (C, num_brokers); the masked reductions run over the (C, max_pairs)
    output slots, not the pending grid. ``counts`` (C, T) supplies the
    engine-maintained member counts so the pass never re-derives them from
    the sID table (see ``_member_counts``).
    """
    C = result.pair_valid.shape[0]
    valid2, rows2, tgt2, cumv, produced, cap_p = _pair_layout(
        result, caps, max_pairs)
    P = valid2.shape[1]
    ch = jnp.arange(C, dtype=jnp.int32)[:, None]
    delivered = jnp.minimum(produced, cap_p)
    q = jnp.broadcast_to(jnp.arange(max_pairs, dtype=jnp.int32), (C, max_pairs))
    p = jnp.minimum(_source_pair(cumv, q), P - 1)          # (C, max_pairs)
    ok = q < delivered[:, None]
    rows = jnp.where(ok, _gather(rows2, p), 0)
    tgts = jnp.where(ok, _gather(tgt2, p), 0)
    out, per_broker = _pack_lines(rows, tgts, ok, ch, group_sids, counts,
                                  payload_words, target_brokers, num_brokers)
    spill_mask = valid2 & (cumv - 1 >= cap_p[:, None])
    return PackedDelivery(out, delivered, produced, spill_mask, per_broker)


def _member_value(group_sids: jnp.ndarray, ch, tgt_safe: jnp.ndarray,
                  j: jnp.ndarray) -> jnp.ndarray:
    """sID of member ``j`` of the pair targeting ``tgt_safe``, per channel."""
    if group_sids.shape[-1] == 0:
        return tgt_safe                     # identity fanout, j is always 0
    return group_sids[ch, tgt_safe, jnp.minimum(j, group_sids.shape[-1] - 1)]


def fanout_sids_all(result: ChannelResult, group_sids: jnp.ndarray,
                    max_notify: int,
                    caps: Optional[jnp.ndarray] = None,
                    counts: Optional[jnp.ndarray] = None) -> FanoutDelivery:
    """Send stage for EVERY channel at once, with per-channel caps. The
    notify buffer is resolved densely (``_dense_ranks``): each pair marks
    the slot its run of members starts at and a prefix max fills the run,
    so the work is O(C * (P + max_notify)), with no member grid and no
    per-slot search. Delivered prefixes are bit-identical to
    ``fanout_sids`` per channel (tables pack members as a -1-padded prefix).
    ``counts`` (C, T): engine-maintained member counts (see
    ``_member_counts``)."""
    return _fanout_parts(result, group_sids, max_notify, caps, counts)[0]


def _fanout_parts(result: ChannelResult, group_sids: jnp.ndarray,
                  max_notify: int, caps,
                  counts: Optional[jnp.ndarray] = None,
                  resident: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None):
    """The send stage plus its internal member bookkeeping, so ``deliver_all``
    can resolve spill and ring-tail ranks against the same prefix sums
    without re-deriving them. ``resident`` — (values (C, W), count (C,)),
    a ring's compacted sID prefix — is delivered ahead of the fresh members:
    combined rank k < count reads ``values[:, k]``, rank k >= count fresh
    member k - count. Returns the delivery and (tgt2, members, cumm, cap)."""
    C = result.pair_valid.shape[0]
    valid2, _, tgt2, _, _, cap_n = _pair_layout(result, caps, max_notify)
    members = _member_counts(group_sids, valid2, tgt2, counts)  # (C, P)
    cumm = jnp.cumsum(members, axis=1)
    shift = (jnp.zeros((C,), jnp.int32) if resident is None
             else resident[1])
    produced = shift + cumm[:, -1]
    delivered = jnp.minimum(produced, cap_n)
    p, j = _dense_ranks(members, cumm, shift, max_notify)
    vals = _pair_member(group_sids, tgt2, p, j)
    k = jnp.arange(max_notify, dtype=jnp.int32)[None, :]
    if resident is not None:
        values, count = resident
        W = values.shape[1]
        head = (values[:, :max_notify] if W >= max_notify else
                jnp.pad(values, ((0, 0), (0, max_notify - W)),
                        constant_values=-1))
        vals = jnp.where(k < count[:, None], head, vals)
    notify = jnp.where(k < delivered[:, None], vals, -1)
    return FanoutDelivery(notify, delivered, produced), (tgt2, members, cumm,
                                                         cap_n)


def _dense_ranks(members: jnp.ndarray, cumm: jnp.ndarray,
                 shift: jnp.ndarray, Q: int
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Owner pair and in-pair offset of EVERY rank k in [0, Q), per channel,
    where pair p's members hold ranks [s_p, s_p + members[p]) with
    s_p = shift + cumm[p] - members[p]. Each non-empty pair writes its index
    at s_p (one scatter over the (C, P) pair grid: empty pairs and starts
    past Q fall to distinct out-of-range slots, so the indices are unique),
    and a prefix max along the slots carries it over its run; a second
    prefix max over the marked slots gives each slot its run's start.
    Slots before the first run (a ring's resident prefix) read owner 0,
    slots past the last run its owner and an offset past its members: the
    caller masks both."""
    C, P = cumm.shape
    ch = jnp.arange(C, dtype=jnp.int32)[:, None]
    pid = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32), (C, P))
    start = shift[:, None] + cumm - members
    dest = jnp.where((members > 0) & (start < Q), start, Q + pid)
    marks = jnp.full((C, Q), -1, jnp.int32).at[ch, dest].set(
        pid, mode="drop", unique_indices=True)
    k = jnp.arange(Q, dtype=jnp.int32)[None, :]
    owner = jax.lax.cummax(marks, axis=1)
    run = jax.lax.cummax(jnp.where(marks >= 0, k, -1), axis=1)
    return jnp.maximum(owner, 0), k - run


def _search_ranks(members: jnp.ndarray, cumm: jnp.ndarray, k: jnp.ndarray
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Owner pair and in-pair offset of per-channel member ranks ``k``
    (C, Q) by binary search over the member prefix sums: the form for a
    short set of sparse ranks (the ring tail)."""
    P = cumm.shape[1]
    p = jnp.minimum(_source_pair(cumm, k), P - 1)
    return p, k - (_gather(cumm, p) - _gather(members, p))


def _pair_member(group_sids, tgt2, p, j) -> jnp.ndarray:
    """sID of member ``j`` of stacked pair ``p`` (both (C, Q))."""
    ch = jnp.arange(tgt2.shape[0], dtype=jnp.int32)[:, None]
    tgt_safe = jnp.maximum(_gather(tgt2, p), 0)
    return _member_value(group_sids, ch, tgt_safe, j)


def _member_lookup(group_sids, tgt2, members, cumm, k, ok) -> jnp.ndarray:
    """Resolve sparse per-channel member ranks ``k`` (C, Q) to sIDs by
    search. -1 where not ``ok``."""
    p, j = _search_ranks(members, cumm, k)
    return jnp.where(ok, _pair_member(group_sids, tgt2, p, j), -1)


def deliver_all(result: ChannelResult, group_sids: jnp.ndarray,
                payload_words: int, max_pairs: int, max_notify: int,
                spill_cap: int,
                caps_pairs: Optional[jnp.ndarray] = None,
                caps_notify: Optional[jnp.ndarray] = None,
                target_brokers: Optional[jnp.ndarray] = None,
                num_brokers: int = 0,
                counts: Optional[jnp.ndarray] = None,
                ring: Optional[RetryRing] = None,
                epochs: Optional[jnp.ndarray] = None) -> FusedDelivery:
    """The whole fused convert+send, plus spill capture: everything that
    missed a delivery buffer lands — with its channel identity — in a flat
    channel-major spill stream holding up to ``spill_cap`` entries PER
    CHANNEL per lane (the first ``spill_cap`` overflow entries of each
    channel are always captured; the rest are truncated for the caller to
    count as drops — one channel's overflow can never crowd out another's,
    which also makes the capture exactly what the per-channel path at C == 1
    would capture). Spill slots gather their entry straight from the
    per-channel overflow windows — spill work is O(C * spill_cap),
    independent of the pending grid. Pure and jit-compatible — the engine
    runs it inside the same jitted call as candidate discovery and the
    joins.

    With ``ring`` (+ ``epochs``, the (C,) current subscription epoch per
    channel) the call is RING-AWARE: resident ring entries whose epoch still
    matches are delivered FIRST (stale ones are dropped and counted), fresh
    result pairs follow, and the live overflow tail re-enters the output
    ring up to its window — only what overflows PAST the ring reaches the
    spill streams (the host queue as bounded last resort). ``counts``
    threads the engine-maintained member counts through both stages.

    The stages run under named scopes, which the device trace reads:
    ``bad.convert`` (the pairs lane up to the wire lines), ``bad.send``
    (the sIDs lane up to the notify buffer) and ``bad.ring`` (both lanes'
    overflow tails into the successor ring and the spill streams)."""
    if ring is not None:
        return _deliver_with_ring(result, group_sids, payload_words,
                                  max_pairs, max_notify, spill_cap, ring,
                                  epochs, caps_pairs, caps_notify,
                                  target_brokers, num_brokers, counts)
    with jax.named_scope("bad.convert"):
        pack = pack_payloads_all(result, group_sids, payload_words,
                                 max_pairs, caps_pairs, target_brokers,
                                 num_brokers, counts)
    with jax.named_scope("bad.ring"):
        valid2, rows2, tgt2, cumv, produced, cap_p = _pair_layout(
            result, caps_pairs, max_pairs)
        P = valid2.shape[1]

        # pairs lane: spill slot (c, i) -> in-channel pair rank cap_c + i ->
        # source pair, by binary search + gather
        ov_p = produced - pack.delivered                       # (C,)
        ch_r, k_r, valid_r, total_p = _spill_slots(ov_p, cap_p, spill_cap)
        pr = _row_search(cumv, P + 1, ch_r, k_r)
        take = lambda arr2: jnp.where(valid_r, arr2[ch_r, pr], -1)
        pair_spill = plans.PairStream(take(rows2),
                                      jnp.where(valid_r, ch_r, -1),
                                      take(tgt2), valid_r, total_p)

    with jax.named_scope("bad.send"):
        # sids lane: same scheme over the send stage's member prefix sums
        fan, (tgt2, members, cumm, cap_n) = _fanout_parts(
            result, group_sids, max_notify, caps_notify, counts)
    with jax.named_scope("bad.ring"):
        ov_s = fan.produced - fan.delivered
        ch_s, k_s, valid_s, total_s = _spill_slots(ov_s, cap_n, spill_cap)
        sid_cap = 1 if group_sids.shape[-1] == 0 else group_sids.shape[-1]
        p_s = _row_search(cumm, P * sid_cap + 1, ch_s, k_s)
        j_s = k_s - (cumm[ch_s, p_s] - members[ch_s, p_s])
        tgt_s = jnp.maximum(tgt2[ch_s, p_s], 0)
        vals = jnp.where(valid_s,
                         _member_value(group_sids, ch_s, tgt_s, j_s), -1)
        sid_spill = plans.ValueStream(vals, jnp.where(valid_s, ch_s, -1),
                                      valid_s, total_s)
    return FusedDelivery(pack, fan, pair_spill, sid_spill)


def _deliver_with_ring(result: ChannelResult, group_sids: jnp.ndarray,
                       payload_words: int, max_pairs: int, max_notify: int,
                       spill_cap: int, ring: RetryRing, epochs: jnp.ndarray,
                       caps_pairs, caps_notify, target_brokers,
                       num_brokers: int, counts) -> FusedDelivery:
    """Ring-aware fused delivery. Per channel, the delivery order is: live
    (epoch-matching) ring entries in residence order, then the fresh valid
    pairs in ravel order. The live overflow tail — ranks past the cap —
    re-enters the output ring (first W entries), then the spill stream
    (next spill_cap), then truncates to counted drops. The wire lines and
    the tails are resolved by search against the ring's live prefix sums
    and the fresh prefix sums; the notify buffer reads the resident sIDs
    as its first slots and resolves the fresh members densely behind them,
    so the added work is O(C * (W + max_pairs + spill_cap))."""
    C = result.pair_valid.shape[0]
    W = ring.window
    epochs = jnp.asarray(epochs, jnp.int32)
    ch = jnp.arange(C, dtype=jnp.int32)[:, None]
    identity = group_sids.shape[-1] == 0

    # ---- pairs lane -----------------------------------------------------
    with jax.named_scope("bad.convert"):
        valid2, rows2, tgt2, cumv, nfresh, cap_p = _pair_layout(
            result, caps_pairs, max_pairs)
        P = valid2.shape[1]
        iw = jnp.arange(W, dtype=jnp.int32)[None, :]
        in_ring = iw < ring.pair_count[:, None]
        live_r = in_ring & (ring.pair_epochs == epochs[:, None])
        cumr = jnp.cumsum(live_r.astype(jnp.int32), axis=1)    # (C, W)
        nring = cumr[:, -1]
        stale = ring.pair_count - nring
        produced = ring.pair_count + nfresh
        delivered = jnp.minimum(nring + nfresh, cap_p)

        def comb_pairs(q, ok):
            """(rows, tgts) for combined-order ranks ``q`` (C, Q): ring
            entries first, fresh pairs after."""
            from_ring = q < nring[:, None]
            pr = jnp.minimum(_source_pair(cumr, q), W - 1)
            r_rows = _gather(ring.pair_rows, pr)
            r_tgts = _gather(ring.pair_targets, pr)
            qf = jnp.maximum(q - nring[:, None], 0)
            pf = jnp.minimum(_source_pair(cumv, qf), P - 1)
            rows = jnp.where(from_ring, r_rows, _gather(rows2, pf))
            tgts = jnp.where(from_ring, r_tgts, _gather(tgt2, pf))
            return jnp.where(ok, rows, -1), jnp.where(ok, tgts, -1)

        q = jnp.broadcast_to(jnp.arange(max_pairs, dtype=jnp.int32),
                             (C, max_pairs))
        ok = q < delivered[:, None]
        rows_q, tgts_q = comb_pairs(q, ok)
        out, per_broker = _pack_lines(
            jnp.where(ok, rows_q, 0), jnp.where(ok, tgts_q, 0), ok, ch,
            group_sids, counts, payload_words, target_brokers, num_brokers)
        pack = PackedDelivery(out, delivered, produced,
                              jnp.zeros_like(valid2), per_broker)

    # live overflow tail -> output ring window, then spill stream
    with jax.named_scope("bad.ring"):
        ov_live = nring + nfresh - delivered                   # (C,)
        i_new = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32), (C, W))
        ok_new = i_new < jnp.minimum(ov_live, W)[:, None]
        nrows, ntgts = comb_pairs(delivered[:, None] + i_new, ok_new)
        ring_p_count = jnp.minimum(ov_live, W)
        r = jnp.arange(C * spill_cap, dtype=jnp.int32)
        ch_r, i_r = r // spill_cap, r % spill_cap
        valid_r = (W + i_r) < ov_live[ch_r]
        # spill ranks start at delivered + W >= W >= nring, so spill slots
        # are always FRESH-sourced: ring entries either deliver or re-enter
        # the ring; they never demote to the host queue
        k_r = delivered[ch_r] + W + i_r             # combined-order rank
        pf_r = _row_search(cumv, P + 1, ch_r, k_r - nring[ch_r])
        sp_rows = rows2[ch_r, pf_r]
        sp_tgts = tgt2[ch_r, pf_r]
        total_p = jnp.sum(jnp.maximum(ov_live - W, 0))
        pair_spill = plans.PairStream(
            jnp.where(valid_r, sp_rows, -1), jnp.where(valid_r, ch_r, -1),
            jnp.where(valid_r, sp_tgts, -1), valid_r, total_p)

    # ---- sids lane ------------------------------------------------------
    with jax.named_scope("bad.send"):
        rsc = ring.sid_count
        fan, (tgt2, members, cumm, cap_n) = _fanout_parts(
            result, group_sids, max_notify, caps_notify, counts,
            resident=(ring.sid_values, rsc))
        produced_s, delivered_s = fan.produced, fan.delivered
    with jax.named_scope("bad.ring"):

        def comb_sids(k, ok):
            """sIDs for the sparse combined-order ranks ``k`` (C, W) of
            the ring tail: resident ring sids (a compacted prefix: direct
            index) first, fresh members after, by search."""
            from_ring = k < rsc[:, None]
            r_val = _gather(ring.sid_values, jnp.minimum(k, W - 1))
            kf = jnp.maximum(k - rsc[:, None], 0)
            f_val = _member_lookup(group_sids, tgt2, members, cumm, kf, ok)
            return jnp.where(ok, jnp.where(from_ring, r_val, f_val), -1)

        ov_s = produced_s - delivered_s
        ok_snew = i_new < jnp.minimum(ov_s, W)[:, None]
        nsids = comb_sids(delivered_s[:, None] + i_new, ok_snew)
        ring_s_count = jnp.minimum(ov_s, W)
        valid_s = (W + i_r) < ov_s[ch_r]
        # same invariant as the pairs lane: rsc <= W, so spill slots are
        # always fresh member lookups
        k_s = delivered_s[ch_r] + W + i_r
        sid_cap = 1 if identity else group_sids.shape[-1]
        kf_s = k_s - rsc[ch_r]
        p_s = _row_search(cumm, P * sid_cap + 1, ch_r, kf_s)
        j_s = kf_s - (cumm[ch_r, p_s] - members[ch_r, p_s])
        tgt_s = jnp.maximum(tgt2[ch_r, p_s], 0)
        vals = jnp.where(valid_s,
                         _member_value(group_sids, ch_r, tgt_s, j_s), -1)
        total_s = jnp.sum(jnp.maximum(ov_s - W, 0))
        sid_spill = plans.ValueStream(vals, jnp.where(valid_s, ch_r, -1),
                                      valid_s, total_s)

        new_ring = RetryRing(
            nrows, ntgts,
            jnp.broadcast_to(epochs[:, None], (C, W)).astype(jnp.int32),
            ring_p_count, nsids, ring_s_count)
        counters = RingCounters(ring.pair_count, stale, ring_p_count,
                                rsc, ring_s_count)
    return FusedDelivery(pack, fan, pair_spill, sid_spill, new_ring,
                         counters)


def _row_search(cum2: jnp.ndarray, offset: int, ch: jnp.ndarray,
                k: jnp.ndarray) -> jnp.ndarray:
    """``searchsorted(cum2[ch_i], k_i, 'right')`` for per-slot channels, as
    ONE global search over the offset-flattened prefix array (``offset`` >
    any row value makes it non-decreasing across row boundaries) — avoids a
    (slots x P) dynamic-row gather that a vmapped per-element search would
    materialize."""
    C, P = cum2.shape
    flat = (cum2 + offset * jnp.arange(C, dtype=jnp.int32)[:, None]).ravel()
    idx = jnp.searchsorted(flat, k + offset * ch, side="right")
    return jnp.clip(idx.astype(jnp.int32) - ch * P, 0, P - 1)


def _spill_slots(ov: jnp.ndarray, cap, spill_cap: int):
    """Per-channel spill windows flattened channel-major: slot r = c *
    spill_cap + i holds channel c's i-th overflow entry (in-channel rank
    cap_c + i), valid while i < min(ov_c, spill_cap). Identical capture to
    running the per-channel path with the same ``spill_cap`` — no
    cross-channel crowd-out. ``total`` is the full (pre-truncation) overflow
    across channels."""
    C = ov.shape[0]
    r = jnp.arange(C * spill_cap, dtype=jnp.int32)
    ch = r // spill_cap
    i = r % spill_cap
    return ch, cap[ch] + i, i < jnp.minimum(ov, spill_cap)[ch], jnp.sum(ov)


def broker_traffic_summary(result: ChannelResult,
                           delivery: Optional[DeliveryStats] = None
                           ) -> Dict[str, np.ndarray]:
    """Per-broker traffic view of one channel result. With ``delivery`` (the
    DeliveryStats of a deliver=True execution) the summary also carries the
    delivery accounting — delivered / spilled / dropped per stage and the
    per-broker delivered split — so benchmarks surface drops instead of only
    byte counts."""
    out = {
        "bytes_per_broker": np.asarray(result.broker_bytes),
        "results_per_broker": np.asarray(result.broker_results),
        "total_bytes": np.asarray(result.broker_bytes.sum()),
        "total_results": np.asarray(result.num_results),
        "total_notified": np.asarray(result.num_notified),
    }
    if delivery is not None:
        out.update({
            "delivered_pairs": np.asarray(delivery.delivered_pairs),
            "spilled_pairs": np.asarray(delivery.spilled_pairs),
            "dropped_pairs": np.asarray(delivery.dropped_pairs),
            "delivered_sids": np.asarray(delivery.delivered_sids),
            "spilled_sids": np.asarray(delivery.spilled_sids),
            "dropped_sids": np.asarray(delivery.dropped_sids),
            "delivered_pairs_per_broker":
                np.asarray(delivery.delivered_pairs_broker, dtype=np.int64),
        })
    return out
