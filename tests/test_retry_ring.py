"""Device-resident retry ring: overflow re-delivers inside the next
``execute_all`` call (no host round-trip), epoch staleness masks churned
entries, ring overflow cascades to the host SpillQueue as last resort, and
multi-tick DeliveryStats conservation — ring-resident pairs included —
holds against a no-cap oracle engine (delivered sID/pair multiset
equality), ring wraparound included."""
import numpy as np
import pytest

from repro.core.channel import tweets_about_crime, tweets_about_drugs
from repro.core.churn import ChurnWorkload, run_ticks
from repro.core.engine import BADEngine
from repro.core.plans import ExecutionFlags

from conftest import check_delivery_conservation, make_tweets

FLAGS = ExecutionFlags(scan_mode="window", aggregation=True,
                       param_pushdown=True)


def _ring_engine(rng, ring_capacity=64, max_deliver_pairs=16, max_notify=32,
                 n_subs=200, spatial=False, **kw):
    eng = BADEngine(dataset_capacity=4096, index_capacity=1024,
                    max_window=2048, max_candidates=512,
                    brokers=("B1", "B2"), group_cap=8,
                    max_deliver_pairs=max_deliver_pairs,
                    max_notify=max_notify, ring_capacity=ring_capacity, **kw)
    eng.create_channel(tweets_about_drugs())
    if spatial:
        eng.create_channel(tweets_about_crime(1))
        eng.set_user_locations(
            (rng.normal(size=(30, 2)) * 30).astype(np.float32),
            rng.integers(0, 2, 30))
    eng.subscribe_bulk("TweetsAboutDrugs", rng.integers(0, 50, n_subs),
                       rng.integers(0, 2, n_subs))
    return eng


def test_ring_redelivers_without_host_drain(rng):
    """Overflow lands in the ring, NOT the host queue, and the next
    execute_all call re-delivers it on device: retried is counted, the ring
    shrinks by what was delivered, and drain_spilled never has work."""
    eng = _ring_engine(rng, ring_capacity=1 << 12)
    eng.ingest(make_tweets(rng, 400, match_drugs=0.3))
    rep = eng.execute_all(FLAGS, timed=False, deliver=True)["TweetsAboutDrugs"]
    o = rep.overflow
    check_delivery_conservation(o, rep.num_results, rep.num_notified)
    assert o.spilled_pairs > 0 and o.retried_pairs == 0
    assert eng.spill.pending_pairs() + eng.spill.pending_sids() == 0
    assert eng.ring_pending_pairs() == o.spilled_pairs
    assert eng.ring_pending_sids() == o.spilled_sids
    assert not eng.drain_spilled()
    # next tick: NO new records — everything delivered is a ring retry
    total_p, total_s = o.spilled_pairs, o.spilled_sids
    got_p = got_s = 0
    for _ in range(200):
        if eng.ring_pending_pairs() + eng.ring_pending_sids() == 0:
            break
        rep = eng.execute_all(FLAGS, timed=False,
                              deliver=True)["TweetsAboutDrugs"]
        o = rep.overflow
        assert rep.num_results == 0
        check_delivery_conservation(o, 0, 0)
        assert o.retried_pairs > 0 or o.retried_sids > 0
        assert o.dropped_pairs == o.dropped_sids == 0
        got_p += o.delivered_pairs
        got_s += o.delivered_sids
    assert (got_p, got_s) == (total_p, total_s)
    assert eng.spill.pending_pairs() + eng.spill.pending_sids() == 0


def test_ring_epoch_staleness_drops(rng):
    """Churn between ticks bumps the epoch: ring-resident PAIRS go stale and
    drop (counted) at the next presentation instead of indexing a moved
    table; ring sIDs never go stale and still deliver."""
    eng = _ring_engine(rng, ring_capacity=1 << 12)
    eng.ingest(make_tweets(rng, 400, match_drugs=0.3))
    rep = eng.execute_all(FLAGS, timed=False, deliver=True)["TweetsAboutDrugs"]
    spilled_p, spilled_s = rep.overflow.spilled_pairs, rep.overflow.spilled_sids
    assert spilled_p > 0
    eng.subscribe("TweetsAboutDrugs", 3, "B1")          # epoch bump
    dropped = delivered_s = 0
    for _ in range(200):
        if eng.ring_pending_pairs() + eng.ring_pending_sids() == 0:
            break
        rep = eng.execute_all(FLAGS, timed=False,
                              deliver=True)["TweetsAboutDrugs"]
        o = rep.overflow
        check_delivery_conservation(o, rep.num_results, rep.num_notified)
        assert o.delivered_pairs == 0                  # no stale re-pack
        dropped += o.dropped_pairs
        delivered_s += o.delivered_sids
    assert dropped == spilled_p
    assert delivered_s == spilled_s


def test_ring_overflow_cascades_to_host_queue(rng):
    """Overflow past the ring window lands in the host SpillQueue (the
    bounded last resort) — conservation still holds and the two stores
    together hold exactly the overflow."""
    eng = _ring_engine(rng, ring_capacity=8)
    eng.ingest(make_tweets(rng, 400, match_drugs=0.3))
    rep = eng.execute_all(FLAGS, timed=False, deliver=True)["TweetsAboutDrugs"]
    o = rep.overflow
    check_delivery_conservation(o, rep.num_results, rep.num_notified)
    assert o.spilled_pairs > 8                          # ring + queue
    assert eng.ring_pending_pairs() == 8
    assert eng.spill.pending_pairs() == o.spilled_pairs - 8
    assert eng.spill.pending_sids() == o.spilled_sids - 8


def test_flush_rings_hands_entries_to_queue(rng):
    """flush_rings moves ring-resident entries into the host queue (drain
    then re-delivers them); channel drops flush implicitly and drain counts
    the unroutable entries as dropped."""
    eng = _ring_engine(rng, ring_capacity=1 << 12)
    eng.ingest(make_tweets(rng, 400, match_drugs=0.3))
    o = eng.execute_all(FLAGS, timed=False,
                        deliver=True)["TweetsAboutDrugs"].overflow
    eng.flush_rings()
    assert eng.ring_pending_pairs() == 0
    assert eng.spill.pending_pairs() == o.spilled_pairs
    assert eng.spill.pending_sids() == o.spilled_sids
    delivered = 0
    while eng.spill.pending_pairs() + eng.spill.pending_sids() > 0:
        for dr in eng.drain_spilled().values():
            assert dr.stats.dropped_pairs == dr.stats.dropped_sids == 0
            delivered += dr.stats.delivered_pairs + dr.stats.delivered_sids
    assert delivered == o.spilled_pairs + o.spilled_sids


def test_run_ticks_sustained_overflow_zero_drain_calls(rng):
    """Under sustained overflow the ring engine performs ZERO drain_spilled
    host calls across ticks while the host-drain baseline needs them every
    tick; dropped stays zero on both."""
    reports = {}
    for tag, ring in (("ring", 1 << 12), ("host", 0)):
        r = np.random.default_rng(7)
        eng = _ring_engine(r, ring_capacity=ring, n_subs=300)
        wl = [ChurnWorkload("TweetsAboutDrugs", adds_per_tick=0,
                            removes_per_tick=0)]
        rep = run_ticks(eng, wl, 6, r, flags=FLAGS, deliver=True,
                        ingest_per_tick=128,
                        make_batch=lambda rr, n, t0: make_tweets(
                            rr, n, t0=t0, match_drugs=0.3),
                        warmup=2)
        reports[tag] = rep
        assert rep.dropped == 0, tag
    assert reports["ring"].drain_calls == 0
    assert reports["ring"].ring_pending > 0
    assert reports["ring"].queue_pending == 0
    assert reports["host"].drain_calls > 0
    assert reports["host"].ring_pending == 0


def _delivered_content(rep):
    """(pair lines, sids) actually delivered by one fused tick."""
    o = rep.overflow
    pairs = [tuple(line) for line in
             rep.payload[:o.delivered_pairs, :2].tolist()]
    sids = rep.notify[:o.delivered_sids].tolist()
    return pairs, sids


@pytest.mark.parametrize("trial", range(4))
def test_multi_tick_conservation_fuzz_vs_oracle(trial):
    """Seeded fuzz: sustained overflow through capped engines (ring +
    queue cascade, wraparound included) delivers — across ticks plus a
    final flush+drain — exactly the pair/sID multisets a no-cap oracle
    engine delivers per tick. DeliveryStats conservation (ring included)
    holds at every tick."""
    r = np.random.default_rng(100 + trial)
    caps = dict(max_deliver_pairs=int(r.integers(8, 40)),
                max_notify=int(r.integers(16, 80)),
                ring_capacity=int(r.integers(4, 48)))
    engines = {}
    for tag, kw in (("capped", caps),
                    ("oracle", dict(max_deliver_pairs=1 << 14,
                                    max_notify=1 << 16,
                                    ring_capacity=1 << 12))):
        rr = np.random.default_rng(1000 + trial)
        eng = _ring_engine(rr, n_subs=150 + 25 * trial, **kw)
        eng.debug_delivery_buffers = True
        engines[tag] = eng
    want_pairs, want_sids = [], []
    got_pairs, got_sids = [], []
    retried_total = 0
    rng_data = np.random.default_rng(2000 + trial)
    for tick in range(int(r.integers(4, 8))):
        batch = make_tweets(rng_data, int(r.integers(30, 120)),
                            t0=100 * (tick + 1), match_drugs=0.3)
        for tag, eng in engines.items():
            eng.ingest(batch)
            rep = eng.execute_all(FLAGS, timed=False,
                                  deliver=True)["TweetsAboutDrugs"]
            o = rep.overflow
            check_delivery_conservation(o, rep.num_results, rep.num_notified)
            p, s = _delivered_content(rep)
            if tag == "oracle":
                assert o.overflow == 0 and o.retried_pairs == 0
                want_pairs += p
                want_sids += s
            else:
                retried_total += o.retried_pairs + o.retried_sids
                got_pairs += p
                got_sids += s
    # wraparound exercised: ring entries were re-presented at least once
    assert retried_total > 0
    # drain the capped engine completely: ring -> queue -> DrainReports
    eng = engines["capped"]
    eng.flush_rings()
    rounds = 0
    while eng.spill.pending_pairs() + eng.spill.pending_sids() > 0:
        rounds += 1
        assert rounds < 500
        for dr in eng.drain_spilled().values():
            assert dr.stats.dropped_pairs == dr.stats.dropped_sids == 0
            if dr.payload is not None and dr.stats.delivered_pairs:
                got_pairs += [tuple(x) for x in
                              dr.payload[:dr.stats.delivered_pairs,
                                         :2].tolist()]
            if dr.notify is not None and dr.stats.delivered_sids:
                got_sids += dr.notify[:dr.stats.delivered_sids].tolist()
    assert sorted(got_pairs) == sorted(want_pairs)
    assert sorted(got_sids) == sorted(want_sids)


def test_spatial_ring_redelivers_and_goes_stale_on_cohort_change(rng):
    """The spatial join group owns its own ring: identity-fanout overflow
    re-delivers on device; converting the channel to a cohort (epoch bump +
    target-space remap) stales the resident pairs instead of misrouting."""
    eng = _ring_engine(rng, ring_capacity=1 << 12, spatial=True)
    eng.ingest(make_tweets(rng, 400, match_drugs=0.3))
    flags = ExecutionFlags(scan_mode="window")
    rep = eng.execute_all(flags, timed=False, deliver=True)["TweetsAboutCrime1"]
    o = rep.overflow
    check_delivery_conservation(o, rep.num_results, rep.num_notified)
    assert o.spilled_pairs > 0
    assert eng.spill.pending_pairs("TweetsAboutCrime1") == 0
    # second call with no new data: ring retries deliver
    rep = eng.execute_all(flags, timed=False, deliver=True)["TweetsAboutCrime1"]
    assert rep.overflow.retried_pairs == o.spilled_pairs
    assert rep.overflow.delivered_pairs > 0
    # cohort creation remaps the spatial target space -> resident stale
    left_p = rep.overflow.spilled_pairs
    assert left_p > 0
    eng.subscribe_users("TweetsAboutCrime1", np.arange(5))
    rep = eng.execute_all(flags, timed=False, deliver=True)["TweetsAboutCrime1"]
    o = rep.overflow
    check_delivery_conservation(o, rep.num_results, rep.num_notified)
    assert o.dropped_pairs >= left_p     # stale pairs dropped, not misrouted


def test_ring_donation_reuses_buffers_and_preserves_conservation(rng):
    """The fused delivery call donates the presented retry ring: steady
    state reuses the ring allocation in place (pointer-set overlap), and
    the donated path's multi-tick conservation is unchanged — delivered +
    spilled + dropped == produced at every tick, drain included."""
    eng = _ring_engine(rng, ring_capacity=32)
    eng.ingest(make_tweets(rng, 400, match_drugs=0.3))
    rep = eng.execute_all(FLAGS, timed=False, deliver=True)["TweetsAboutDrugs"]
    check_delivery_conservation(rep.overflow, rep.num_results,
                                rep.num_notified)
    [(_, _, ring)] = list(eng._rings.values())
    if not hasattr(ring.pair_rows, "unsafe_buffer_pointer"):
        pytest.skip("jax.Array.unsafe_buffer_pointer unavailable")
    before = {x.unsafe_buffer_pointer() for x in ring}
    for tick in range(4):
        eng.ingest(make_tweets(rng, 60, t0=100 * (tick + 2),
                               match_drugs=0.3))
        rep = eng.execute_all(FLAGS, timed=False,
                              deliver=True)["TweetsAboutDrugs"]
        check_delivery_conservation(rep.overflow, rep.num_results,
                                    rep.num_notified)
        assert rep.overflow.dropped_pairs == 0
        [(_, _, ring)] = list(eng._rings.values())
        after = {x.unsafe_buffer_pointer() for x in ring}
        assert before & after, f"tick {tick}: ring reallocated from scratch"
        before = after
    while eng.spill.pending_pairs() + eng.spill.pending_sids() > 0:
        for dr in eng.drain_spilled().values():
            assert dr.stats.dropped_pairs == dr.stats.dropped_sids == 0


def test_ring_counts_pass_matches_table_derivation(rng):
    """Threading TargetArrays.counts into deliver_all is a pure
    optimization: stats and buffers are identical to deriving the member
    counts from the sID table."""
    import jax.numpy as jnp
    from repro.core.broker import pack_payloads_all, fanout_sids_all
    from conftest import random_stacked_broker_result
    stacked, group_sids, _, _ = random_stacked_broker_result(rng, 3, 16, 3,
                                                             4, 3)
    counts = jnp.sum(jnp.asarray(group_sids) >= 0, axis=-1).astype(jnp.int32)
    a = pack_payloads_all(stacked, jnp.asarray(group_sids), 2, 16)
    b = pack_payloads_all(stacked, jnp.asarray(group_sids), 2, 16,
                          counts=counts)
    np.testing.assert_array_equal(np.asarray(a.payload), np.asarray(b.payload))
    np.testing.assert_array_equal(np.asarray(a.delivered),
                                  np.asarray(b.delivered))
    fa = fanout_sids_all(stacked, jnp.asarray(group_sids), 32)
    fb = fanout_sids_all(stacked, jnp.asarray(group_sids), 32, counts=counts)
    np.testing.assert_array_equal(np.asarray(fa.notify), np.asarray(fb.notify))
    np.testing.assert_array_equal(np.asarray(fa.produced),
                                  np.asarray(fb.produced))


@pytest.mark.parametrize("identity", [False, True], ids=["groups", "identity"])
def test_ring_resident_sids_lead_the_notify_buffer(rng, identity):
    """Ring-aware ``deliver_all`` with resident ring sIDs: per channel, the
    notify buffer holds the resident sIDs first, then the fresh members in
    the order the per-channel ``fanout_sids`` delivers them, cut at the
    cap; the overflow tail refills the output ring, then the spill
    stream."""
    import jax
    import jax.numpy as jnp
    from repro.core.broker import deliver_all, empty_ring, fanout_sids
    from conftest import random_stacked_broker_result
    C, W, max_notify, spill_cap = 3, 8, 24, 4
    stacked, group_sids, _, _ = random_stacked_broker_result(rng, C, 12, 3,
                                                             5, 3)
    if identity:
        group_sids = np.zeros((C, 0), np.int32)
    gs = jnp.asarray(group_sids)
    rsc = np.array([W, 3, 0], np.int32)
    sid_values = np.full((C, W), -1, np.int32)
    for c in range(C):
        sid_values[c, :rsc[c]] = rng.integers(50000, 60000, rsc[c])
    ring = empty_ring(C, W)._replace(sid_values=jnp.asarray(sid_values),
                                     sid_count=jnp.asarray(rsc))
    streams = []
    for c in range(C):
        one = jax.tree.map(lambda a, c=c: a[c], stacked)
        big, dlv, _ = fanout_sids(one, gs[c], 4096)
        streams.append(np.concatenate([sid_values[c, :rsc[c]],
                                       np.asarray(big)[:int(dlv)]]))
    caps = np.array([max(1, len(s) - W - 3) for s in streams], np.int32)
    d = deliver_all(stacked, gs, 2, 16, max_notify, spill_cap,
                    caps_notify=jnp.asarray(caps), ring=ring,
                    epochs=jnp.zeros((C,), jnp.int32))
    spill_ch = np.asarray(d.sid_spill.channels)
    spill_vals = np.asarray(d.sid_spill.values)
    for c, full in enumerate(streams):
        n = min(len(full), int(caps[c]), max_notify)
        exp = np.full(max_notify, -1, np.int32)
        exp[:n] = full[:n]
        np.testing.assert_array_equal(np.asarray(d.fan.notify[c]), exp)
        assert int(d.fan.delivered[c]) == n
        assert int(d.fan.produced[c]) == len(full)
        tail = full[n:]
        r = int(d.ring.sid_count[c])
        assert r == min(len(tail), W)
        np.testing.assert_array_equal(np.asarray(d.ring.sid_values[c, :r]),
                                      tail[:r])
        np.testing.assert_array_equal(spill_vals[spill_ch == c],
                                      tail[W:W + spill_cap])
    assert (spill_ch >= 0).any()
