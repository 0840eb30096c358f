"""Sharded-vs-single-device parity harness for the mesh-sharded BAD engine.

``ShardedBADEngine`` partitions the subscription population over N
device-local engines (channels and the data plane replicate; subscriptions
hash-partition by global sID). The contract these tests pin down: sharding
is a PHYSICAL layout choice — the delivered notification content must be
bit-identical to a single-device engine running the same seeded workload.

Parity is asserted on partition-INdependent observables:

  * the delivered sID multiset (end-subscriber notifications) — always;
  * the delivered (row_id, sID) pair multiset expanded from the payload
    wire lines — whenever no churn lands while entries are ring-resident.
    Under churn + sustained overflow, ring entries whose group epoch moved
    go stale and DROP at re-presentation (pairs re-group; sIDs never go
    stale), so there the capped engines' pair multiset is checked as a
    sub-multiset of the oracle's instead.

Aggregate counts that depend on the grouping itself (``num_results`` — the
same content chops into more, smaller groups under partitioning) are
deliberately NOT compared; ``num_notified`` (produced member sIDs) is
partition-independent and is.

Everything multi-device runs under the conftest-forced
``--xla_force_host_platform_device_count`` host device count and skips
cleanly when the flag could not take effect.
"""
import collections

import numpy as np
import pytest

from repro.core import plans
from repro.core.broker import payload_notifications
from repro.core.channel import tweets_about_crime, tweets_about_drugs
from repro.core.churn import ChurnWorkload, run_ticks
from repro.core.engine import BADEngine
from repro.core.plans import ChannelPlan, ExecutionFlags
from repro.core.sharded import ShardedBADEngine
from repro.distributed import collectives, partition

from conftest import check_delivery_conservation, make_tweets

FLAGS = ExecutionFlags(scan_mode="window", aggregation=True,
                       param_pushdown=True)
PW = 8    # engine default deliver_payload_words

# generous delivery caps: the plan-matrix tests run overflow-free so pair
# content parity is exact (nothing rings, nothing can go stale)
MATRIX_CAPS = dict(dataset_capacity=4096, index_capacity=1024,
                   max_window=1024, max_candidates=512,
                   brokers=("B1", "B2"), group_cap=8,
                   max_deliver_pairs=1 << 12, max_notify=1 << 14,
                   ring_capacity=1 << 10)

# tight per-shard caps: the churn fuzz runs in sustained overflow so the
# ring/spill/drain machinery is exercised on every shard
OVERFLOW_CAPS = dict(dataset_capacity=8192, index_capacity=1024,
                     max_window=2048, max_candidates=512,
                     brokers=("B1", "B2"), group_cap=8,
                     max_deliver_pairs=24, max_notify=48, ring_capacity=256,
                     max_spill=2048, spill_capacity=1 << 15)


def _delivered(rep):
    """Per-tick delivered content from the per-shard debug buffers:
    ((row, sid) pair list, sid list)."""
    pair_rows, sids = [], []
    for r in rep.per_shard:
        o = r.overflow
        pair_rows += [tuple(x) for x in payload_notifications(
            r.payload, o.delivered_pairs, PW).tolist()]
        sids += np.asarray(r.notify)[:o.delivered_sids].tolist()
    return pair_rows, sids


def _drain_content(drain_reports, pair_rows, sids, allow_drops=False):
    """Fold DrainReport content (and assert exactly-once: no drops unless
    the caller expects staleness)."""
    for dr in drain_reports:
        if not allow_drops:
            assert dr.stats.dropped_pairs == dr.stats.dropped_sids == 0
        if dr.payload is not None and dr.stats.delivered_pairs:
            pair_rows += [tuple(x) for x in payload_notifications(
                dr.payload, dr.stats.delivered_pairs, PW).tolist()]
        if dr.notify is not None and dr.stats.delivered_sids:
            sids += dr.notify[:dr.stats.delivered_sids].tolist()


def _settle(eng):
    """Flush every ring through the spill queues and drain to empty;
    returns the drained ((row, sid) pairs, sids). Settling happens against
    unchanged tables, so nothing may drop."""
    pair_rows, sids = [], []
    eng.flush_rings()
    rounds = 0
    while eng.spill.pending_pairs() + eng.spill.pending_sids() > 0:
        rounds += 1
        assert rounds < 500, "drain did not converge"
        _drain_content(eng.drain_spilled().values(), pair_rows, sids)
    assert eng.ring_pending_pairs() + eng.ring_pending_sids() == 0
    return pair_rows, sids


# ---------------------------------------------------------------------------
# plan-matrix parity: 4 scan modes x {aggregated, flat} x {padded, compact}
# ---------------------------------------------------------------------------


def _matrix_run(num_shards, plan):
    """The seeded matrix workload: one param channel under ``plan``, one
    spatial channel riding along, 2 delivered ticks, no overflow."""
    rng = np.random.default_rng(5)
    eng = ShardedBADEngine(num_shards=num_shards, **MATRIX_CAPS)
    eng.debug_delivery_buffers = True
    eng.set_user_locations((rng.normal(size=(40, 2)) * 30).astype(np.float32),
                           rng.integers(0, 2, 40))
    eng.create_channel(tweets_about_drugs())
    eng.create_channel(tweets_about_crime(1))
    eng.subscribe_bulk("TweetsAboutDrugs", rng.integers(0, 50, 250),
                       rng.integers(0, 2, 250))
    eng.set_plan("TweetsAboutDrugs", plan)
    # the spatial channel shares the scan mode; compact backends are a
    # param-join layout, so it stays on the padded family
    eng.set_plan("TweetsAboutCrime1", ChannelPlan(
        scan_mode=plan.scan_mode,
        backend=plan.backend if plan.backend in ("oracle", "pallas")
        else "oracle"))
    pair_rows, sids, notified = [], [], 0
    for tick in range(2):
        eng.ingest(make_tweets(rng, 150, t0=100 * (tick + 1),
                               match_drugs=0.25))
        reps = eng.execute_all(None, timed=False, deliver=True)
        for name, rep in reps.items():
            o = rep.overflow
            check_delivery_conservation(o, rep.num_results, rep.num_notified)
            assert (o.spilled_pairs + o.dropped_pairs + o.spilled_sids
                    + o.dropped_sids) == 0, (name, o)
            p, s = _delivered(rep)
            pair_rows += [(name,) + t for t in p]
            sids += [(name, x) for x in s]
            notified += rep.num_notified
    return pair_rows, sids, notified


@pytest.mark.multidevice
@pytest.mark.parametrize("backend", ["oracle", "compact"])
@pytest.mark.parametrize("aggregation", [True, False])
@pytest.mark.parametrize("scan_mode", plans.SCAN_MODES)
def test_plan_matrix_parity(scan_mode, aggregation, backend):
    """2-way sharded == single-device, content-exact, for every scan mode x
    layout x {padded, compact} backend — with a spatial channel in the same
    engine to cover the cohort partitioning path."""
    plan = ChannelPlan(scan_mode=scan_mode, aggregation=aggregation,
                       param_pushdown=True, backend=backend)
    p1, s1, n1 = _matrix_run(1, plan)
    p2, s2, n2 = _matrix_run(2, plan)
    assert sorted(p1) == sorted(p2)
    assert sorted(s1) == sorted(s2)
    assert n1 == n2
    assert len(s1) > 0    # the workload actually delivered something


# ---------------------------------------------------------------------------
# churn + sustained-overflow fuzz: N in {1, 2, 4} vs a generous-cap oracle
# ---------------------------------------------------------------------------


def _fuzz_run(num_shards, cap_overrides, reshard_at=None, reshard_to=None):
    """6 churn ticks under sustained overflow, then settle to empty.
    Returns (pair multiset, sid multiset, engine)."""
    rng = np.random.default_rng(11)
    kw = dict(OVERFLOW_CAPS)
    kw.update(cap_overrides)
    eng = ShardedBADEngine(num_shards=num_shards, **kw)
    eng.debug_delivery_buffers = True
    eng.create_channel(tweets_about_drugs())
    live = list(eng.subscribe_bulk("TweetsAboutDrugs",
                                   rng.integers(0, 50, 200),
                                   rng.integers(0, 2, 200)))
    pair_rows, sids = [], []
    for tick in range(6):
        new = eng.subscribe_bulk("TweetsAboutDrugs",
                                 rng.integers(0, 50, 40),
                                 rng.integers(0, 2, 40))
        live += list(new)
        rm = [live.pop(rng.integers(0, len(live))) for _ in range(20)]
        eng.remove_subscriptions("TweetsAboutDrugs", np.asarray(rm))
        eng.ingest(make_tweets(rng, 120, t0=100 * (tick + 1),
                               match_drugs=0.3))
        rep = eng.execute_all(FLAGS, timed=False,
                              deliver=True)["TweetsAboutDrugs"]
        check_delivery_conservation(rep.overflow, rep.num_results,
                                    rep.num_notified)
        p, s = _delivered(rep)
        pair_rows += p
        sids += s
        if reshard_at == tick:
            # mid-stream migration: rings flush + drain against the OLD
            # engines; the drained content stays part of the delivery stream
            _drain_content(eng.reshard(reshard_to).values(), pair_rows, sids)
    p, s = _settle(eng)
    return pair_rows + p, sids + s, eng


@pytest.fixture(scope="module")
def fuzz_oracle():
    """Single-device generous-cap run of the fuzz workload: nothing ever
    overflows, so its delivered content is the ground-truth multiset."""
    pair_rows, sids, eng = _fuzz_run(1, dict(max_deliver_pairs=1 << 13,
                                             max_notify=1 << 15,
                                             ring_capacity=1 << 12))
    assert len(sids) > 500    # the workload is not degenerate
    return pair_rows, sids


@pytest.mark.multidevice
@pytest.mark.parametrize("num_shards", [1, 2, 4])
def test_churn_overflow_fuzz_vs_oracle(num_shards, fuzz_oracle):
    """Capped N-way sharded engines under churn + sustained overflow
    deliver exactly the oracle's sID multiset (notifications are never
    lost, duplicated, or misrouted), conserve per tick, and drain to empty.
    Pair content: a sub-multiset of the oracle's — churned ring-resident
    PAIRS go stale by design (their grouping moved) while their sIDs are
    re-sent; nothing may appear that the oracle did not produce."""
    oracle_pairs, oracle_sids = fuzz_oracle
    pair_rows, sids, eng = _fuzz_run(num_shards, {})
    assert sorted(sids) == sorted(oracle_sids)
    extra = collections.Counter(pair_rows) - collections.Counter(oracle_pairs)
    assert not extra, f"pairs not produced by the oracle: {extra}"
    # everything drained: global conservation closed out
    assert eng.ring_pending_pairs() + eng.ring_pending_sids() == 0
    assert eng.spill.pending_pairs() + eng.spill.pending_sids() == 0


@pytest.mark.multidevice
def test_reshard_ring_flush_conservation(fuzz_oracle):
    """Resharding 2 -> 4 mid-stream (rings populated) loses nothing: the
    flush-drain-migrate protocol keeps the delivered sID multiset exactly
    equal to the oracle's, and the re-partitioned live population matches
    the host registry shard-by-shard."""
    oracle_pairs, oracle_sids = fuzz_oracle
    pair_rows, sids, eng = _fuzz_run(2, {}, reshard_at=2, reshard_to=4)
    assert eng.num_shards == 4
    assert sorted(sids) == sorted(oracle_sids)
    extra = collections.Counter(pair_rows) - collections.Counter(oracle_pairs)
    assert not extra
    # re-partition dropped no live subscription: the union of the shards'
    # aggregator-held sIDs is the registry population, each on its hash shard
    live = eng.live_sids("TweetsAboutDrugs")
    per_shard = eng.shard_live_sids("TweetsAboutDrugs")
    got = np.sort(np.concatenate(per_shard)) if per_shard else live[:0]
    np.testing.assert_array_equal(got, live)
    owner = partition.shard_for_sids(live, 4)
    for i, shard_sids in enumerate(per_shard):
        np.testing.assert_array_equal(shard_sids, np.sort(live[owner == i]))


# ---------------------------------------------------------------------------
# steady state: zero retraces per shard
# ---------------------------------------------------------------------------


@pytest.mark.multidevice
def test_zero_steady_state_retraces_per_shard():
    """After warmup, steady churned ticks patch device state in place on
    every shard: per-shard traces and rebuilds stay flat while patches
    advance (the epoch/delta protocol survives the sharded control plane)."""
    rng = np.random.default_rng(9)
    eng = ShardedBADEngine(num_shards=4, **MATRIX_CAPS)
    eng.create_channel(tweets_about_drugs())
    live = list(eng.subscribe_bulk("TweetsAboutDrugs",
                                   rng.integers(0, 50, 300),
                                   rng.integers(0, 2, 300)))
    def churn_tick(tick):
        new = eng.subscribe_bulk("TweetsAboutDrugs",
                                 rng.integers(0, 50, 32),
                                 rng.integers(0, 2, 32))
        live.extend(new)
        rm = [live.pop(rng.integers(0, len(live))) for _ in range(32)]
        eng.remove_subscriptions("TweetsAboutDrugs", np.asarray(rm))
        eng.ingest(make_tweets(rng, 100, t0=1000 * (tick + 1),
                               match_drugs=0.25))
        eng.execute_all(FLAGS, timed=False, deliver=True)

    for tick in range(2):    # churned warmup: traces + first capacity sizing
        churn_tick(tick)
    snaps = eng.per_shard_maintenance()
    for tick in range(2, 6):
        churn_tick(tick)
    deltas = [e.maintenance.since(s)
              for e, s in zip(eng.shards, snaps)]
    assert [d.traces for d in deltas] == [0] * 4
    assert [d.rebuilds for d in deltas] == [0] * 4
    assert sum(d.patches for d in deltas) > 0


# ---------------------------------------------------------------------------
# cross-shard notification routing (the collective shuffle)
# ---------------------------------------------------------------------------


@pytest.mark.multidevice
def test_shuffle_notify_matches_ref(multidevice):
    """The shard_map all-gather shuffle is bit-identical to the host
    reference on random -1-padded buffers, and every routed sID lands on
    the shard that owns it."""
    rng = np.random.default_rng(21)
    mesh = collectives.notify_mesh(4)
    for trial in range(5):
        sids = rng.integers(0, 1000, (4, 24)).astype(np.int32)
        sids[rng.random((4, 24)) < 0.4] = -1
        owners = np.where(sids >= 0,
                          rng.integers(0, 4, (4, 24)), -1).astype(np.int32)
        got = np.asarray(collectives.shuffle_notify(mesh, sids, owners))
        want = collectives.shuffle_notify_ref(sids, owners, 4)
        np.testing.assert_array_equal(got, want)
        by_owner = {o: sids[(owners == o) & (sids >= 0)]
                    for o in range(4)}
        for o in range(4):
            row = got[o][got[o] >= 0]
            assert sorted(row.tolist()) == sorted(by_owner[o].tolist())


@pytest.mark.multidevice
def test_routed_delivery_preserves_sids():
    """With ``route_cross_shard`` on, each tick's routed buffers hold
    exactly the delivered sID multiset, grouped onto broker-owner shards
    (row o only carries sIDs whose broker endpoint shard is o)."""
    rng = np.random.default_rng(13)
    eng = ShardedBADEngine(num_shards=4, route_cross_shard=True,
                           **MATRIX_CAPS)
    eng.create_channel(tweets_about_drugs())
    eng.subscribe_bulk("TweetsAboutDrugs", rng.integers(0, 50, 300),
                       rng.integers(0, 2, 300))
    total = 0
    for tick in range(2):
        eng.ingest(make_tweets(rng, 150, t0=100 * (tick + 1),
                               match_drugs=0.3))
        rep = eng.execute_all(FLAGS, timed=False,
                              deliver=True)["TweetsAboutDrugs"]
        assert rep.routed is not None
        assert rep.routed.shape[0] == 4
        _, sids = _delivered(rep)
        routed = rep.routed[rep.routed >= 0]
        assert sorted(routed.tolist()) == sorted(sids)
        brokers = eng._reg["TweetsAboutDrugs"].brokers
        for o in range(4):
            row = rep.routed[o][rep.routed[o] >= 0]
            if row.size:
                owners = partition.broker_owner(brokers[row], 4)
                assert (owners == o).all()
        total += len(sids)
    assert total > 0


@pytest.mark.multidevice
def test_more_shards_than_devices_is_refused(multidevice):
    """A shard count the runtime has no devices for is an error: shards
    never double up on a device, and routing never falls back to the host
    reference (``shuffle_notify_ref`` is only the tests' oracle)."""
    n = len(multidevice) + 1
    with pytest.raises(ValueError, match="devices"):
        ShardedBADEngine(num_shards=n, **MATRIX_CAPS)
    with pytest.raises(RuntimeError, match="devices"):
        collectives.notify_mesh(n)
    eng = ShardedBADEngine(num_shards=2, route_cross_shard=True,
                           **MATRIX_CAPS)
    with pytest.raises(ValueError, match="devices"):
        eng.reshard(n)
    assert eng.num_shards == 2
    assert list(eng._mesh.devices.flat) == multidevice[:2]


# ---------------------------------------------------------------------------
# facade anchors (device-count independent)
# ---------------------------------------------------------------------------


def test_facade_matches_plain_engine():
    """num_shards=1 facade == plain BADEngine, buffer-exact: the sharded
    control plane adds global sID allocation and nothing else."""
    def drive(eng):
        rng = np.random.default_rng(17)
        eng.debug_delivery_buffers = True
        eng.create_channel(tweets_about_drugs())
        eng.subscribe_bulk("TweetsAboutDrugs", rng.integers(0, 50, 120),
                           rng.integers(0, 2, 120))
        out = []
        for tick in range(2):
            eng.ingest(make_tweets(rng, 100, t0=100 * (tick + 1),
                                   match_drugs=0.25))
            out.append(eng.execute_all(FLAGS, timed=False,
                                       deliver=True)["TweetsAboutDrugs"])
        return out
    plain = drive(BADEngine(**MATRIX_CAPS))
    facade = drive(ShardedBADEngine(num_shards=1, **MATRIX_CAPS))
    for p, f in zip(plain, facade):
        s = f.per_shard[0]
        assert f.num_results == p.num_results
        assert f.num_notified == p.num_notified
        assert f.overflow == p.overflow
        np.testing.assert_array_equal(np.asarray(s.payload),
                                      np.asarray(p.payload))
        np.testing.assert_array_equal(np.asarray(s.notify),
                                      np.asarray(p.notify))


@pytest.mark.multidevice
def test_drop_channel_leaves_other_partitions_intact():
    """Dropping one channel leaves the other channel's partitioned
    population untouched (registry == union of shard aggregators, each on
    its hash shard), and the dropped name can be re-created and
    re-subscribed."""
    rng = np.random.default_rng(23)
    eng = ShardedBADEngine(num_shards=4, **MATRIX_CAPS)
    eng.create_channel(tweets_about_drugs())
    eng.create_channel(tweets_about_crime(1))
    eng.subscribe_bulk("TweetsAboutDrugs", rng.integers(0, 50, 200),
                       rng.integers(0, 2, 200))
    crime = eng.subscribe_bulk("TweetsAboutCrime1",
                               rng.integers(0, 50, 100),
                               rng.integers(0, 2, 100))
    eng.remove_subscriptions("TweetsAboutCrime1", crime[:40])
    before = eng.live_sids("TweetsAboutCrime1")
    eng.drop_channel("TweetsAboutDrugs")
    np.testing.assert_array_equal(eng.live_sids("TweetsAboutCrime1"), before)
    per_shard = eng.shard_live_sids("TweetsAboutCrime1")
    np.testing.assert_array_equal(np.sort(np.concatenate(per_shard)), before)
    owner = partition.shard_for_sids(before, 4)
    for i, shard_sids in enumerate(per_shard):
        np.testing.assert_array_equal(shard_sids, np.sort(before[owner == i]))
    # the dropped name is reusable; execution still runs on the survivor
    eng.create_channel(tweets_about_drugs())
    eng.subscribe_bulk("TweetsAboutDrugs", rng.integers(0, 50, 50),
                       rng.integers(0, 2, 50))
    eng.ingest(make_tweets(rng, 80, t0=500, match_drugs=0.3))
    reps = eng.execute_all(FLAGS, timed=False, deliver=True)
    assert set(reps) == {"TweetsAboutDrugs", "TweetsAboutCrime1"}


@pytest.mark.multidevice
def test_churn_driver_through_facade():
    """The sustained-churn driver runs unmodified against the sharded
    facade (capped, so the ring/spill path is live) and loses nothing."""
    rng = np.random.default_rng(3)
    eng = ShardedBADEngine(num_shards=4, **OVERFLOW_CAPS)
    eng.create_channel(tweets_about_drugs())
    wl = [ChurnWorkload("TweetsAboutDrugs", adds_per_tick=64,
                        removes_per_tick=32)]
    rep = run_ticks(
        eng, wl, 5, rng, flags=FLAGS, deliver=True, ingest_per_tick=64,
        make_batch=lambda rr, n, t0: make_tweets(rr, n, t0=t0,
                                                 match_drugs=0.3),
        warmup=2)
    assert rep.adds > 0 and rep.removes > 0
    assert rep.delivered_sids > 0
    assert rep.subs_per_s > 0
