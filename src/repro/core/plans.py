"""Executable channel plans: original vs the three optimizations (paper §4).

All plan functions are pure and jit-compatible (static shapes, masked
windows). The engine binds them with static ``ExecutionFlags``:

scan_mode (how candidate records are found)          -- paper Fig. 11
  "full"       full dataset scan + is_new timestamp filter   (original, no index)
  "window"     delta scan of records since last execution    (ts-ordered storage)
  "trad_index" traditional secondary index on the single most selective fixed
               predicate: candidates = that predicate's matches, remaining
               predicates evaluated at query time
  "bad_index"  the BAD index: precomputed full-conjunction matches + watermark
aggregation     join against subscription-groups instead of raw subscriptions
param_pushdown  early semi-join with UserParameters           -- paper Fig. 9(b)
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import bad_index as bidx
from repro.core import records as R
from repro.core.predicates import CompiledConditions, apply_op, evaluate_conditions
from repro.core.user_params import semi_join

SCAN_MODES = ("full", "window", "trad_index", "bad_index")
# Kernel backends come in two families (oracle = pure jnp, pallas = the
# Pallas kernels) x two join formulations: padded ("oracle"/"pallas" — the
# stacked C x shape-bucket x member-cap pair grid) and compacted
# ("compact"/"compact_pallas" — the flat CSR candidate stream below, where
# join cost scales with LIVE candidates instead of padding).
BACKENDS = ("oracle", "pallas", "compact", "compact_pallas")


def backend_family(backend: str) -> str:
    """The kernel family ("oracle" | "pallas") of any backend name."""
    return "pallas" if backend in ("pallas", "compact_pallas") else "oracle"


def is_compact(backend: str) -> bool:
    """True for the compacted-stream join formulation."""
    return backend in ("compact", "compact_pallas")


def compact_variant(backend: str) -> str:
    """The compacted-stream backend of the given backend's family."""
    return "compact_pallas" if backend_family(backend) == "pallas" \
        else "compact"


@dataclasses.dataclass(frozen=True)
class ExecutionFlags:
    scan_mode: str = "window"
    aggregation: bool = False
    param_pushdown: bool = False

    def __post_init__(self):
        if self.scan_mode not in SCAN_MODES:
            raise ValueError(f"scan_mode must be one of {SCAN_MODES}")

    @staticmethod
    def original() -> "ExecutionFlags":
        return ExecutionFlags(scan_mode="full")

    @staticmethod
    def fully_optimized() -> "ExecutionFlags":
        return ExecutionFlags(scan_mode="bad_index", aggregation=True,
                              param_pushdown=True)


@dataclasses.dataclass(frozen=True)
class ChannelPlan:
    """A channel's full physical plan: scan mode x target layout x kernel
    backend. ``ExecutionFlags`` names the paper's three optimizations;
    ``ChannelPlan`` extends it with the backend axis and is the unit the
    engine partitions ``execute_all`` by — channels sharing a plan run in
    ONE fused jitted call, distinct plans run as separate plan-groups
    (each with its own stacked caches and retry ring, keyed by the plan).
    """

    scan_mode: str = "window"
    aggregation: bool = False
    param_pushdown: bool = False
    backend: str = "oracle"
    # dispatch-time enrichment tag: the attached EnrichmentStage's hashable
    # ``identity`` (core/enrich.py), stamped by the engine when a stage is
    # active so every plan-keyed cache (compiled executables, stream
    # buckets, retry rings, warm signatures) keys on the scorer too — a
    # scorer attach/detach/swap retraces and re-rings exactly like a plan
    # switch. Never assigned to ``ChannelState.plan`` and never persisted
    # (``to_dict`` omits it).
    scorer: Optional[tuple] = None

    def __post_init__(self):
        if self.scan_mode not in SCAN_MODES:
            raise ValueError(f"scan_mode must be one of {SCAN_MODES}")
        if self.backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}")

    @property
    def flags(self) -> "ExecutionFlags":
        """The ExecutionFlags view (everything but the backend axis)."""
        return ExecutionFlags(self.scan_mode, self.aggregation,
                              self.param_pushdown)

    @staticmethod
    def from_flags(flags: "ExecutionFlags",
                   backend: str = "oracle") -> "ChannelPlan":
        return ChannelPlan(flags.scan_mode, flags.aggregation,
                           flags.param_pushdown, backend)

    def to_dict(self) -> dict:
        return {"scan_mode": self.scan_mode, "aggregation": self.aggregation,
                "param_pushdown": self.param_pushdown, "backend": self.backend}

    @staticmethod
    def from_dict(d: dict) -> "ChannelPlan":
        return ChannelPlan(d["scan_mode"], bool(d["aggregation"]),
                           bool(d["param_pushdown"]), d.get("backend", "oracle"))


def enumerate_plans(backends=("oracle",), param_pushdown: bool = True):
    """Every static (scan mode x layout x backend) combination — the search
    space of the offline plan seeder and the planner-vs-static benchmark."""
    return tuple(ChannelPlan(scan, agg, param_pushdown, b)
                 for b in backends for scan in SCAN_MODES
                 for agg in (False, True))


@dataclasses.dataclass(frozen=True)
class ExecutionRequest:
    """The single execution spec behind ``BADEngine.execute``/``dispatch``.

    One request subsumes what used to be three overlapping entry points:

      * ``flags`` — the legacy homogeneous mode: every requested channel
        runs ``ChannelPlan.from_flags(flags, backend)``; routed through the
        SAME plan-group machinery as everything else (one synthetic group).
      * ``plan`` — an explicit homogeneous ``ChannelPlan`` (full physical
        plan, backend included). Mutually exclusive with ``flags``.
      * neither — the planner-driven mode: channels run their assigned
        ``ChannelPlan`` (``set_plan``) or the engine default, partitioned
        into plan-groups.

    ``backend`` overrides the kernel backend on whatever plan the above
    resolves to (the old ``execute_channel(backend=...)`` knob, now
    available on the fused path). ``channels`` restricts execution to a
    subset (None = all); restricted dispatches leave the other groups'
    retry rings resident. The remaining fields carry the per-call execution
    options previously spread across keyword arguments."""

    flags: Optional[ExecutionFlags] = None
    plan: Optional[ChannelPlan] = None
    backend: Optional[str] = None
    channels: Optional[tuple] = None
    advance: bool = True
    timed: bool = False
    deliver: bool = False
    resolve_spills: bool = False

    def __post_init__(self):
        if self.flags is not None and self.plan is not None:
            raise ValueError("pass flags or plan, not both")
        if self.backend is not None and self.backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}")
        if self.channels is not None:
            object.__setattr__(self, "channels", tuple(self.channels))

    def forced_plan(self, default_backend: str) -> Optional[ChannelPlan]:
        """The homogeneous plan this request forces on every requested
        channel, or None for the per-channel-assignment mode (where a
        ``backend`` override, if any, is applied per channel)."""
        if self.plan is not None:
            return (self.plan if self.backend is None
                    else dataclasses.replace(self.plan, backend=self.backend))
        if self.flags is not None:
            return ChannelPlan.from_flags(self.flags,
                                          self.backend or default_backend)
        return None


class TargetArrays(NamedTuple):
    """Device-side join targets: either raw subscriptions or groups."""

    params: jnp.ndarray        # (T,) int32
    brokers: jnp.ndarray       # (T,) int32
    counts: jnp.ndarray        # (T,) int32  (1 for raw subscriptions)
    by_param: jnp.ndarray      # (domain, maxT) int32, -1 padded
    by_param_count: jnp.ndarray  # (domain,) int32


class CandidateSet(NamedTuple):
    rows: jnp.ndarray      # (Rmax,) int32 row ids
    valid: jnp.ndarray     # (Rmax,) bool
    scanned: jnp.ndarray   # () int32 -- records examined (cost accounting)


class ChannelResult(NamedTuple):
    pair_rows: jnp.ndarray     # (Rmax, maxT) int32 record row of each result pair
    pair_targets: jnp.ndarray  # (Rmax, maxT) int32 target (sub or group) index
    pair_valid: jnp.ndarray    # (Rmax, maxT) bool
    matched_rows: jnp.ndarray  # (Rmax,) int32 candidate rows that matched preds
    matched_valid: jnp.ndarray  # (Rmax,) bool
    num_results: jnp.ndarray   # () int32 -- result records produced (pairs)
    num_notified: jnp.ndarray  # () int32 -- end subscribers covered
    scanned: jnp.ndarray       # () int32
    broker_bytes: jnp.ndarray  # (B,) i32 platform->broker traffic (bytes)
    broker_results: jnp.ndarray  # (B,) int32 results per broker


# ---------------------------------------------------------------------------
# Step 1: candidate discovery
# ---------------------------------------------------------------------------


def candidates_full_scan(ds: R.ActiveDataset, conds_one: CompiledConditions,
                         last_ts: jnp.ndarray, max_rows: int) -> CandidateSet:
    """Original plan: scan the whole dataset, is_new() via timestamp compare,
    then evaluate every fixed predicate at query time."""
    cap = ds.capacity
    slots = jnp.arange(cap, dtype=jnp.int32)
    row_ids = _slot_row_ids(ds, slots)
    live = (row_ids >= 0) & (row_ids < ds.size)
    ts = ds.fields[:, R.TIMESTAMP]
    is_new = ts > last_ts
    match = evaluate_conditions(ds.fields, conds_one)[:, 0]
    keep = live & is_new & match
    rows, valid = _compact(row_ids, keep, max_rows)
    return CandidateSet(rows, valid, jnp.asarray(cap, jnp.int32))


def candidates_window(ds: R.ActiveDataset, conds_one: CompiledConditions,
                      last_size: jnp.ndarray, max_rows: int) -> CandidateSet:
    """Delta scan: only records ingested since last execution (ts-ordered)."""
    row_ids = last_size + jnp.arange(max_rows, dtype=jnp.int32)
    in_range = row_ids < ds.size
    slots = row_ids % ds.capacity
    fields = ds.fields[slots]
    match = evaluate_conditions(fields, conds_one)[:, 0]
    keep = in_range & match
    return CandidateSet(jnp.where(keep, row_ids, -1), keep,
                        jnp.minimum(ds.size - last_size, max_rows).astype(jnp.int32))


def candidates_trad_index(ds: R.ActiveDataset, conds_one: CompiledConditions,
                          best_pred: int, last_size: jnp.ndarray,
                          max_rows: int, max_candidates: int) -> CandidateSet:
    """Traditional secondary index on the most selective fixed predicate:
    the index returns rows matching that ONE predicate (compacted — this is
    the index read), remaining predicates are evaluated on the candidates."""
    row_ids = last_size + jnp.arange(max_rows, dtype=jnp.int32)
    in_range = row_ids < ds.size
    slots = row_ids % ds.capacity
    fields = ds.fields[slots]
    fi = conds_one.field_idx[0, best_pred]
    op = conds_one.op[0, best_pred]
    val = conds_one.value[0, best_pred]
    idx_hit = apply_op(fields[:, fi], jnp.asarray(op), jnp.asarray(val)) & in_range
    cand_rows, cand_valid = _compact(row_ids, idx_hit, max_candidates)
    # Evaluate the remaining predicates only on index candidates.
    cfields = ds.fields[jnp.maximum(cand_rows, 0) % ds.capacity]
    match = evaluate_conditions(cfields, conds_one)[:, 0]
    keep = cand_valid & match
    return CandidateSet(jnp.where(keep, cand_rows, -1), keep,
                        jnp.sum(idx_hit.astype(jnp.int32)))


def candidates_bad_index(ds: R.ActiveDataset, index: bidx.BADIndexState,
                         channel: int, max_rows: int) -> CandidateSet:
    """BAD-index plan: fixed predicates were already evaluated at ingestion;
    read only entries newer than the watermark. No re-evaluation."""
    rows, valid = bidx.new_entries(index, channel, max_rows)
    return CandidateSet(rows, valid, jnp.sum(valid.astype(jnp.int32)))


# ---------------------------------------------------------------------------
# Step 2+3: (optional) UserParameters semi-join, then the target join
# ---------------------------------------------------------------------------


def join_param_targets(ds: R.ActiveDataset, cand: CandidateSet,
                       targets: TargetArrays, param_field: int,
                       payload_bytes: int, num_brokers: int,
                       up_mask: Optional[jnp.ndarray],
                       aggregated: bool,
                       domain: Optional[jnp.ndarray] = None,
                       fused: bool = False) -> ChannelResult:
    """record[param_field] == target.param join via the dense by_param map.

    ``domain`` overrides the clip bound when ``targets`` is padded to a
    shared shape bucket (fused multi-channel execution): the channel's *real*
    parameter domain must bound the clip so padded rows never join.
    ``fused`` switches broker accounting to a one-hot contraction — under
    vmap, segment_sum lowers to serialized scatter-adds; unvmapped, the
    scatter is fine and the dense (Rm, maxT, B) one-hot would cost memory.
    """
    slots = jnp.maximum(cand.rows, 0) % ds.capacity
    pvals = ds.fields[slots, param_field]                   # (Rm,)
    valid = cand.valid
    if up_mask is not None:
        valid = valid & semi_join(pvals, up_mask)           # Fig. 9(b) early join
    if domain is None:
        domain = targets.by_param.shape[0]
    pv = jnp.clip(pvals, 0, domain - 1)
    tgt = targets.by_param[pv]                              # (Rm, maxT)
    tgt_n = targets.by_param_count[pv]                      # (Rm,)
    maxT = tgt.shape[1]
    pair_valid = valid[:, None] & (jnp.arange(maxT)[None, :] < tgt_n[:, None]) & (tgt >= 0)
    tgt_safe = jnp.maximum(tgt, 0)
    pair_rows = jnp.where(pair_valid, cand.rows[:, None], -1)
    pair_targets = jnp.where(pair_valid, tgt, -1)
    members = jnp.where(pair_valid, targets.counts[tgt_safe], 0)  # subscribers per pair
    num_results = jnp.sum(pair_valid.astype(jnp.int32))
    num_notified = jnp.sum(members.astype(jnp.int32))
    # Platform->broker traffic: one payload per result pair; aggregated pairs
    # additionally carry the member sID list (4 B each) -- paper §4.1.2.
    # Byte totals accumulate in int32 end-to-end (exact to 2^31 bytes per
    # (channel, broker) per tick; float32 would silently round past 2^24).
    per_pair_bytes = payload_bytes + (4 * members if aggregated else jnp.zeros_like(members))
    pair_bytes = jnp.where(pair_valid, per_pair_bytes, 0).astype(jnp.int32)
    bids = jnp.where(pair_valid, targets.brokers[tgt_safe], num_brokers)
    if fused:
        # Per-broker masked reductions: each is an (Rm, maxT) elementwise
        # select + sum that XLA fuses without materializing a dense
        # (Rm, maxT, B) one-hot. Invalid pairs carry the sentinel id
        # == num_brokers and match no broker; counts stay integer end-to-end
        # (float32 accumulation would silently round past 2^24 pairs).
        broker_bytes = jnp.stack(
            [jnp.sum(jnp.where(bids == b, pair_bytes, 0))
             for b in range(num_brokers)])
        broker_results = jnp.stack(
            [jnp.sum((bids == b).astype(jnp.int32))
             for b in range(num_brokers)])
    else:
        broker_bytes = jax.ops.segment_sum(pair_bytes.ravel(), bids.ravel(),
                                           num_segments=num_brokers + 1)[:-1]
        broker_results = jax.ops.segment_sum(
            pair_valid.astype(jnp.int32).ravel(), bids.ravel(),
            num_segments=num_brokers + 1)[:-1]
    return ChannelResult(pair_rows, pair_targets, pair_valid,
                         jnp.where(valid, cand.rows, -1), valid,
                         num_results, num_notified, cand.scanned,
                         broker_bytes, broker_results)


def join_spatial(ds: R.ActiveDataset, cand: CandidateSet,
                 user_locations: jnp.ndarray, user_brokers: jnp.ndarray,
                 radius, payload_bytes, num_brokers: int,
                 spatial_fn=None, fused: bool = False) -> ChannelResult:
    """spatial_distance(user.location, record.location) < radius join
    (TweetsAboutCrime). ``spatial_fn`` lets the engine swap in the Pallas
    kernel; default is the pure-jnp oracle. ``fused`` switches broker
    accounting to masked per-broker reductions (segment_sum serializes under
    vmap), exactly as in ``join_param_targets``."""
    slots = jnp.maximum(cand.rows, 0) % ds.capacity
    locs = ds.location[slots]                              # (Rm, 2)
    if spatial_fn is None:
        from repro.kernels.spatial_match import ref as spatial_ref
        hits = spatial_ref.spatial_match(locs, user_locations, radius)
    else:
        hits = spatial_fn(locs, user_locations, radius)    # (Rm, U) bool
    pair_valid = hits & cand.valid[:, None]
    U = user_locations.shape[0]
    pair_rows = jnp.where(pair_valid, cand.rows[:, None], -1)
    pair_targets = jnp.where(pair_valid, jnp.arange(U, dtype=jnp.int32)[None, :], -1)
    num_results = jnp.sum(pair_valid.astype(jnp.int32))
    bids = jnp.where(pair_valid, user_brokers[None, :], num_brokers)
    pair_bytes = jnp.where(pair_valid, payload_bytes, 0).astype(jnp.int32)
    if fused:
        broker_bytes = jnp.stack(
            [jnp.sum(jnp.where(bids == b, pair_bytes, 0))
             for b in range(num_brokers)])
        broker_results = jnp.stack(
            [jnp.sum((bids == b).astype(jnp.int32))
             for b in range(num_brokers)])
    else:
        broker_bytes = jax.ops.segment_sum(pair_bytes.ravel(), bids.ravel(),
                                           num_segments=num_brokers + 1)[:-1]
        broker_results = jax.ops.segment_sum(pair_valid.astype(jnp.int32).ravel(),
                                             bids.ravel(),
                                             num_segments=num_brokers + 1)[:-1]
    return ChannelResult(pair_rows, pair_targets, pair_valid,
                         jnp.where(cand.valid, cand.rows, -1), cand.valid,
                         num_results, num_results, cand.scanned,
                         broker_bytes, broker_results)


# ---------------------------------------------------------------------------
# Fused multi-channel execution: every stacked function returns pytrees with a
# leading channel axis, so one jitted call drives all channels (paper scale
# goal: many channels x many subscribers with no per-channel host round-trip).
# ---------------------------------------------------------------------------


def _eval_channel_row(fields: jnp.ndarray, field_idx: jnp.ndarray,
                      op: jnp.ndarray, value: jnp.ndarray) -> jnp.ndarray:
    """(N, F) records x ONE channel's padded predicate row (P,) -> (N,) bool."""
    vals = fields[:, field_idx]                    # (N, P)
    return jnp.all(apply_op(vals, op[None], value[None]), axis=-1)


def candidates_full_scan_all(ds: R.ActiveDataset, conds: CompiledConditions,
                             last_ts: jnp.ndarray, max_rows: int,
                             match_fn=None) -> CandidateSet:
    """Stacked 'full' scan: ONE conditionsList pass covers every channel
    (the per-channel variant re-evaluates its own conjunction per call).
    ``match_fn``: optional (N, F) -> (N, C) evaluator (the Pallas
    ``predicate_filter`` kernel); default is the jnp oracle."""
    cap = ds.capacity
    slots = jnp.arange(cap, dtype=jnp.int32)
    row_ids = _slot_row_ids(ds, slots)
    live = (row_ids >= 0) & (row_ids < ds.size)
    ts = ds.fields[:, R.TIMESTAMP]
    if match_fn is None:
        match = evaluate_conditions(ds.fields, conds)      # (cap, C)
    else:
        match = match_fn(ds.fields)

    def one(last_ts_c, match_c):
        keep = live & (ts > last_ts_c) & match_c
        rows, valid = _compact(row_ids, keep, max_rows)
        return CandidateSet(rows, valid, jnp.asarray(cap, jnp.int32))

    return jax.vmap(one)(last_ts, match.T)


def candidates_window_all(ds: R.ActiveDataset, conds: CompiledConditions,
                          last_size: jnp.ndarray, max_rows: int,
                          match_fn=None) -> CandidateSet:
    """Stacked delta scan: each channel reads its own [last_size, size) window.
    ``match_fn``: optional (C, W, F) -> (C, W) evaluator of channel c's
    conjunction on its own gathered row block (``predicate_filter_rows``);
    default is the vmapped jnp oracle."""
    row_ids = last_size[:, None] + jnp.arange(max_rows, dtype=jnp.int32)[None, :]
    in_range = row_ids < ds.size                           # (C, W)
    fields = ds.fields[row_ids % ds.capacity]              # (C, W, F)
    match = _match_rows(fields, conds, match_fn)
    keep = in_range & match
    scanned = jnp.minimum(ds.size - last_size, max_rows).astype(jnp.int32)
    return CandidateSet(jnp.where(keep, row_ids, -1), keep, scanned)


def candidates_trad_index_all(ds: R.ActiveDataset, conds: CompiledConditions,
                              best_pred: jnp.ndarray, last_size: jnp.ndarray,
                              max_rows: int, max_candidates: int,
                              match_fn=None) -> CandidateSet:
    """Stacked traditional-index scan: per channel, the index read is its most
    selective fixed predicate; the rest evaluate on the candidates (via
    ``match_fn`` with the same (C, N, F) -> (C, N) contract as
    ``candidates_window_all``)."""
    field_idx = jnp.asarray(conds.field_idx)
    op = jnp.asarray(conds.op)
    value = jnp.asarray(conds.value)

    def index_read(best_c, last_size_c, fi_row, op_row, val_row):
        row_ids = last_size_c + jnp.arange(max_rows, dtype=jnp.int32)
        in_range = row_ids < ds.size
        fields = ds.fields[row_ids % ds.capacity]
        idx_hit = apply_op(fields[:, fi_row[best_c]], op_row[best_c],
                           val_row[best_c]) & in_range
        cand_rows, cand_valid = _compact(row_ids, idx_hit, max_candidates)
        return cand_rows, cand_valid, jnp.sum(idx_hit.astype(jnp.int32))

    cand_rows, cand_valid, scanned = jax.vmap(index_read)(
        best_pred, last_size, field_idx, op, value)
    cfields = ds.fields[jnp.maximum(cand_rows, 0) % ds.capacity]  # (C, Rc, F)
    keep = cand_valid & _match_rows(cfields, conds, match_fn)
    return CandidateSet(jnp.where(keep, cand_rows, -1), keep, scanned)


def _match_rows(fields: jnp.ndarray, conds: CompiledConditions,
                match_fn) -> jnp.ndarray:
    """(C, N, F) stacked row blocks -> (C, N): channel c's conjunction on its
    own block, via ``match_fn`` (Pallas) or the vmapped jnp oracle."""
    if match_fn is not None:
        return match_fn(fields)
    return jax.vmap(_eval_channel_row)(fields, jnp.asarray(conds.field_idx),
                                       jnp.asarray(conds.op),
                                       jnp.asarray(conds.value))


def candidates_bad_index_all(index: bidx.BADIndexState, channels: jnp.ndarray,
                             max_rows: int) -> CandidateSet:
    """Stacked BAD-index read: every channel's watermark window at once."""

    def one(c):
        rows, valid = bidx.new_entries(index, c, max_rows)
        return CandidateSet(rows, valid, jnp.sum(valid.astype(jnp.int32)))

    return jax.vmap(one)(channels)


def join_param_targets_all(ds: R.ActiveDataset, cand: CandidateSet,
                           targets: TargetArrays, param_field: jnp.ndarray,
                           payload_bytes: jnp.ndarray, num_brokers: int,
                           up_mask: Optional[jnp.ndarray], aggregated: bool,
                           domain: jnp.ndarray) -> ChannelResult:
    """vmapped ``join_param_targets`` over the channel axis.

    ``cand``/``targets``/``up_mask``/scalars carry a leading C axis; targets
    are shape-bucketed (padded to the max T / domain / fan-out across
    channels) with -1 / 0 padding that can never produce a valid pair.
    """

    def one(cand_c, targets_c, up_mask_c, pf_c, pb_c, dom_c):
        return join_param_targets(
            ds, cand_c, targets_c, pf_c, pb_c, num_brokers,
            up_mask_c if up_mask is not None else None, aggregated, dom_c,
            fused=True)

    um = up_mask if up_mask is not None else jnp.zeros(
        (cand.rows.shape[0], 1), dtype=bool)
    return jax.vmap(one)(cand, targets, um, param_field, payload_bytes, domain)


def join_spatial_all(ds: R.ActiveDataset, cand: CandidateSet,
                     user_locations: jnp.ndarray, user_brokers: jnp.ndarray,
                     radius: jnp.ndarray, payload_bytes: jnp.ndarray,
                     num_brokers: int, spatial_fn=None) -> ChannelResult:
    """vmapped ``join_spatial`` over the channel axis (TweetsAboutCrime at
    fused scale).

    ``cand`` carries a leading C axis; ``user_locations`` (C, U, 2) /
    ``user_brokers`` (C, U) are the stacked per-channel user sets,
    shape-bucketed by the engine with far-sentinel padding (padded users can
    never fall inside any radius); ``radius`` / ``payload_bytes`` are
    per-channel (C,) scalars. ``spatial_fn`` (e.g. the Pallas ``spatial_match``
    wrapper) is batched by vmap — pallas_call lowers the channel axis onto a
    leading grid dimension, so the whole join stays one fused device call.
    """

    def one(cand_c, locs_c, brokers_c, radius_c, payload_c):
        return join_spatial(ds, cand_c, locs_c, brokers_c, radius_c,
                            payload_c, num_brokers, spatial_fn, fused=True)

    return jax.vmap(one)(cand, user_locations, user_brokers, radius,
                         payload_bytes)


# ---------------------------------------------------------------------------
# Flat pair streams: the stacked (C, ...) pair axes as ONE channel-major
# stream proportional to total pending work instead of C x max-pending.
# PairStream/ValueStream are the wire types of the broker's fused spill
# capture (dropped pairs/sIDs keep their channel identity; the broker fills
# them by per-channel-window gathers). The flatten_* builders below are the
# standalone scatter-compaction API over arbitrary masks — exercised by the
# property suites. The compacted execution join (CandStream and the
# join_*_stream functions further down) routes the fused join itself through
# the same formulation.
# ---------------------------------------------------------------------------


class PairStream(NamedTuple):
    """Flat channel-major (row, channel, target) pair stream.

    ``valid`` marks the live slots; ``total`` is the pre-truncation count
    across ALL channels. ``flatten_pairs_all`` emits a compacted in-order
    prefix (``sum(valid) == min(total, max_total)``); the broker's spill
    capture emits per-channel windows (each channel's in-order overflow
    prefix, up to its window size). Invalid slots hold -1.
    """

    rows: jnp.ndarray       # (P,) int32
    channels: jnp.ndarray   # (P,) int32
    targets: jnp.ndarray    # (P,) int32
    valid: jnp.ndarray      # (P,) bool
    total: jnp.ndarray      # () int32


class ValueStream(NamedTuple):
    """Flat channel-major (value, channel) stream (e.g. overflowed sIDs);
    same ``valid``/``total`` semantics as ``PairStream``."""

    values: jnp.ndarray     # (P,) int32
    channels: jnp.ndarray   # (P,) int32
    valid: jnp.ndarray      # (P,) bool
    total: jnp.ndarray      # () int32


def _compact_flat_indices(mask: jnp.ndarray, out_size: int):
    """Indices of set mask positions, compacted in order into ``out_size``
    slots. Returns (idx, valid, total); positions past the buffer are dropped
    (never aliased onto the last slot), exactly like ``_compact``."""
    n = mask.shape[0]
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
    dest = jnp.where(mask, pos, out_size)
    idx = jnp.zeros((out_size + 1,), dtype=jnp.int32)
    idx = idx.at[dest].set(jnp.arange(n, dtype=jnp.int32), mode="drop")
    total = jnp.sum(mask.astype(jnp.int32))
    valid = jnp.arange(out_size, dtype=jnp.int32) < total
    return idx[:out_size], valid, total


def flatten_pairs_all(pair_rows: jnp.ndarray, pair_targets: jnp.ndarray,
                      mask: jnp.ndarray, max_total: int) -> PairStream:
    """Compact a stacked (C, ...) masked pair set into one flat channel-major
    (row, channel, target) stream of at most ``max_total`` entries.

    Work downstream of this stream is proportional to the TOTAL pending pairs
    across channels, not ``C x max-pending`` — the shape-bucketed stacked
    layout's padding never survives the compaction.
    """
    C = pair_rows.shape[0]
    rows = pair_rows.reshape(C, -1)
    tgts = pair_targets.reshape(C, -1)
    per = rows.shape[1]
    idx, valid, total = _compact_flat_indices(mask.reshape(-1), max_total)
    neg = jnp.full_like(idx, -1)
    return PairStream(
        jnp.where(valid, rows.reshape(-1)[idx], neg),
        jnp.where(valid, (idx // per).astype(jnp.int32), neg),
        jnp.where(valid, tgts.reshape(-1)[idx], neg),
        valid, total)


def flatten_result_pairs(result: ChannelResult, max_total: int) -> PairStream:
    """The stacked fused-join output as a compacted flat pair stream: every
    valid (record row, channel, target) pair across all channels, in
    channel-major delivery order."""
    return flatten_pairs_all(result.pair_rows, result.pair_targets,
                             result.pair_valid, max_total)


def flatten_values_all(values: jnp.ndarray, mask: jnp.ndarray,
                       max_total: int) -> ValueStream:
    """Compact stacked (C, M) masked values into one flat channel-major
    (value, channel) stream of at most ``max_total`` entries."""
    C = values.shape[0]
    vals = values.reshape(C, -1)
    per = vals.shape[1]
    idx, valid, total = _compact_flat_indices(mask.reshape(-1), max_total)
    neg = jnp.full_like(idx, -1)
    return ValueStream(
        jnp.where(valid, vals.reshape(-1)[idx], neg),
        jnp.where(valid, (idx // per).astype(jnp.int32), neg),
        valid, total)


# ---------------------------------------------------------------------------
# Compacted execution join: the "compact"/"compact_pallas" backends. After
# stacked discovery, live candidates across ALL channels compact into one flat
# channel-major CandStream (the same CSR prefix-sum/scatter formulation as
# flatten_pairs_all); the param/spatial join, member-count gather, and broker
# accounting then run over that stream, so execution cost scales with live
# candidates instead of the padded C x shape-bucket grid. stream_to_stacked
# re-presents the stream join as a standard stacked ChannelResult (contiguous
# per-channel segments), so deliver_all — ring semantics, per-channel caps,
# conservation — runs verbatim; because the compaction is stable and
# channel-major, each channel's valid pairs appear in EXACTLY the padded
# path's ravel order, making delivery pair-for-pair identical under caps.
# ---------------------------------------------------------------------------


class CandStream(NamedTuple):
    """Flat channel-major compacted candidate stream.

    ``counts`` / ``total`` are PRE-truncation (sum over the discovery masks):
    ``total > rows.shape[0]`` means the stream overflowed its static capacity
    and the caller must re-run with a larger one (the engine's grow-on-
    overflow protocol — a truncated stream's results are never used).
    ``channels`` is 0 on invalid slots (safe as a gather index)."""

    rows: jnp.ndarray      # (S,) int32 record row ids, -1 on invalid slots
    channels: jnp.ndarray  # (S,) int32 owning channel, 0 on invalid slots
    valid: jnp.ndarray     # (S,) bool
    counts: jnp.ndarray    # (C,) int32 per-channel live counts
    total: jnp.ndarray     # () int32


class StreamJoin(NamedTuple):
    """Per-entry join output over a CandStream: (S, maxT) pair grids plus
    per-channel (C,) accounting, ready for ``stream_to_stacked``."""

    pair_rows: jnp.ndarray       # (S, maxT) int32
    pair_targets: jnp.ndarray    # (S, maxT) int32
    pair_valid: jnp.ndarray      # (S, maxT) bool
    matched_rows: jnp.ndarray    # (S,) int32
    matched_valid: jnp.ndarray   # (S,) bool
    num_results: jnp.ndarray     # (C,) int32
    num_notified: jnp.ndarray    # (C,) int32
    broker_bytes: jnp.ndarray    # (C, B) int32
    broker_results: jnp.ndarray  # (C, B) int32


def compact_candidates(cand: CandidateSet, max_total: int) -> CandStream:
    """Compact a stacked (C, Rm) CandidateSet into one flat channel-major
    stream of at most ``max_total`` live candidates. Stable: within a
    channel, candidates keep their discovery order."""
    C, Rm = cand.rows.shape
    idx, valid, total = _compact_flat_indices(cand.valid.reshape(-1),
                                              max_total)
    rows = jnp.where(valid, cand.rows.reshape(-1)[idx], -1)
    channels = jnp.where(valid, (idx // Rm).astype(jnp.int32), 0)
    counts = jnp.sum(cand.valid.astype(jnp.int32), axis=1)
    return CandStream(rows, channels, valid, counts, total)


def join_param_stream(ds: R.ActiveDataset, stream: CandStream,
                      targets: TargetArrays, param_field: jnp.ndarray,
                      payload_bytes: jnp.ndarray, num_brokers: int,
                      up_mask: Optional[jnp.ndarray], aggregated: bool,
                      domain: jnp.ndarray, join_fn=None) -> StreamJoin:
    """``join_param_targets_all`` over a compacted stream: every gather is
    per stream ENTRY (channel id -> that channel's stacked tables), so work
    is O(S x maxT) instead of O(C x Rm x maxT). ``targets`` and the
    (C,)-shaped scalars are the same stacked inputs the padded path uses.
    ``join_fn`` is the pair-expansion hook (``kernels/join_compact``): the
    jnp ref by default, the Pallas kernel under "compact_pallas"."""
    if join_fn is None:
        from repro.kernels.join_compact import ref as jc_ref
        join_fn = jc_ref.join_pairs
    ch = stream.channels
    slots = jnp.maximum(stream.rows, 0) % ds.capacity
    pvals = ds.fields[slots, param_field[ch]]               # (S,)
    valid = stream.valid
    if up_mask is not None:
        # per-entry semi_join (Fig. 9(b)): same clip/in-domain semantics
        dom_max = up_mask.shape[1]
        clipped = jnp.clip(pvals, 0, dom_max - 1)
        in_dom = (pvals >= 0) & (pvals < dom_max)
        valid = valid & up_mask[ch, clipped] & in_dom
    pv = jnp.clip(pvals, 0, domain[ch] - 1)
    tgt = targets.by_param[ch, pv]                          # (S, maxT)
    tgt_n = targets.by_param_count[ch, pv]                  # (S,)
    tgt_safe = jnp.maximum(tgt, 0)
    members_tbl = targets.counts[ch[:, None], tgt_safe]     # (S, maxT)
    bids_tbl = targets.brokers[ch[:, None], tgt_safe]       # (S, maxT)
    pair_valid, members, pair_bytes, bids = join_fn(
        tgt, tgt_n, members_tbl, bids_tbl, valid, payload_bytes[ch],
        num_brokers, aggregated)
    pair_rows = jnp.where(pair_valid, stream.rows[:, None], -1)
    pair_targets = jnp.where(pair_valid, tgt, -1)
    return StreamJoin(
        pair_rows, pair_targets, pair_valid,
        jnp.where(valid, stream.rows, -1), valid,
        *_stream_accounting(ch, pair_valid, members, pair_bytes, bids,
                            param_field.shape[0], num_brokers))


def join_spatial_stream(ds: R.ActiveDataset, stream: CandStream,
                        user_locations: jnp.ndarray, user_brokers: jnp.ndarray,
                        radius: jnp.ndarray, payload_bytes: jnp.ndarray,
                        num_brokers: int) -> StreamJoin:
    """``join_spatial_all`` over a compacted stream: each entry gathers its
    channel's user set and evaluates the euclidean oracle formula — the
    same formula as the ``spatial_match`` kernel, which is tied to the
    per-channel dense layout; the compact family keeps the jnp form for both
    backends, so compacted spatial results are bitwise identical to the
    padded path)."""
    ch = stream.channels
    slots = jnp.maximum(stream.rows, 0) % ds.capacity
    locs = ds.location[slots]                               # (S, 2)
    ulocs = user_locations[ch]                              # (S, U, 2)
    d = locs[:, None, :] - ulocs
    hits = jnp.sum(d * d, axis=-1) < radius[ch][:, None] ** 2
    pair_valid = hits & stream.valid[:, None]               # (S, U)
    U = user_locations.shape[1]
    pair_rows = jnp.where(pair_valid, stream.rows[:, None], -1)
    pair_targets = jnp.where(
        pair_valid, jnp.arange(U, dtype=jnp.int32)[None, :], -1)
    members = pair_valid.astype(jnp.int32)
    pair_bytes = jnp.where(pair_valid, payload_bytes[ch][:, None],
                           0).astype(jnp.int32)
    bids = jnp.where(pair_valid, user_brokers[ch], num_brokers)
    num_results, num_notified, broker_bytes, broker_results = \
        _stream_accounting(ch, pair_valid, members, pair_bytes, bids,
                           user_locations.shape[0], num_brokers)
    return StreamJoin(pair_rows, pair_targets, pair_valid,
                      jnp.where(stream.valid, stream.rows, -1), stream.valid,
                      num_results, num_results, broker_bytes, broker_results)


def _stream_accounting(ch: jnp.ndarray, pair_valid: jnp.ndarray,
                       members: jnp.ndarray, pair_bytes: jnp.ndarray,
                       bids: jnp.ndarray, num_channels: int,
                       num_brokers: int):
    """Per-channel result/notify/broker accounting over a flat stream: ONE
    segment_sum per quantity with segment = channel x (broker + sentinel)
    (unvmapped, so the scatter-add lowering is fine; invalid pairs carry the
    sentinel broker id == num_brokers, dropped by the slice)."""
    nb1 = num_brokers + 1
    seg = ch[:, None] * nb1 + bids                          # (S, maxT)
    broker_bytes = jax.ops.segment_sum(
        pair_bytes.ravel(), seg.ravel(),
        num_segments=num_channels * nb1).reshape(
            num_channels, nb1)[:, :-1]
    pvc = pair_valid.astype(jnp.int32)
    broker_results = jax.ops.segment_sum(
        pvc.ravel(), seg.ravel(),
        num_segments=num_channels * nb1).reshape(
            num_channels, nb1)[:, :-1]
    num_results = jax.ops.segment_sum(jnp.sum(pvc, axis=1), ch,
                                      num_segments=num_channels)
    num_notified = jax.ops.segment_sum(jnp.sum(members, axis=1), ch,
                                       num_segments=num_channels)
    return num_results, num_notified, broker_bytes, broker_results


def stream_to_stacked(sj: StreamJoin, stream: CandStream,
                      scanned: jnp.ndarray, width: int) -> ChannelResult:
    """Re-present a stream join as a stacked (C, width, maxT) ChannelResult.

    The stream is channel-major, so channel c's entries are the contiguous
    segment [off_c, off_c + counts_c) — a plain offset gather rebuilds the
    per-channel view, preserving within-channel pair order exactly.
    ``width`` need only bound the largest per-channel live count (<= the
    discovery buffer width), NOT the stream size, so the stacked view never
    exceeds the padded grid's footprint. Only meaningful when the stream did
    not truncate (``total <= S``) — the engine discards overflowed runs."""
    S = stream.rows.shape[0]
    off = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                           jnp.cumsum(stream.counts)[:-1].astype(jnp.int32)])
    k = jnp.arange(width, dtype=jnp.int32)
    src = off[:, None] + k[None, :]                         # (C, width)
    ok = (k[None, :] < stream.counts[:, None]) & (src < S)
    srcc = jnp.minimum(src, S - 1)
    pair_valid = sj.pair_valid[srcc] & ok[..., None]
    return ChannelResult(
        jnp.where(pair_valid, sj.pair_rows[srcc], -1),
        jnp.where(pair_valid, sj.pair_targets[srcc], -1),
        pair_valid,
        jnp.where(ok, sj.matched_rows[srcc], -1),
        sj.matched_valid[srcc] & ok,
        sj.num_results, sj.num_notified, scanned,
        sj.broker_bytes, sj.broker_results)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _slot_row_ids(ds: R.ActiveDataset, slots: jnp.ndarray) -> jnp.ndarray:
    """Stable row id currently stored in each ring slot (-1 if never used)."""
    size = ds.size
    cap = ds.capacity
    base = (size - 1 - slots) // cap * cap + slots   # largest id == slot (mod cap) and < size
    return jnp.where(size > slots % cap, base, -1).astype(jnp.int32)


def _compact(row_ids: jnp.ndarray, mask: jnp.ndarray,
             out_size: int):
    """Stable masked compaction into a fixed-size buffer."""
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
    dest = jnp.where(mask, pos, out_size)
    out = jnp.full((out_size,), -1, dtype=jnp.int32)
    out = out.at[jnp.minimum(dest, out_size)].set(
        jnp.where(mask, row_ids, -1), mode="drop")
    valid = jnp.arange(out_size, dtype=jnp.int32) < jnp.sum(mask.astype(jnp.int32))
    return out, valid
