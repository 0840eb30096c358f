"""Fused broker delivery + spill/retry: conservation, exactly-once drain,
fused/per-channel parity, flat pair-stream compaction (seeded fuzz; the
hypothesis variants in test_property.py run the same shared checkers)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.broker import fanout_sids, pack_payloads, pack_payloads_all
from repro.core.channel import (most_threatening_tweets, tweets_about_crime,
                                tweets_about_drugs)
from repro.core.engine import BADEngine, SpillQueue
from repro.core.plans import (ExecutionFlags, flatten_pairs_all,
                              flatten_result_pairs, flatten_values_all)

from conftest import (check_deliver_all_invariants,
                      check_delivery_conservation, make_tweets,
                      random_stacked_broker_result)


def _overflow_engine(rng, max_deliver_pairs=16, max_notify=32, max_spill=1024,
                     spill_capacity=1 << 16, **kw):
    eng = BADEngine(dataset_capacity=2048, index_capacity=1024,
                    max_window=1024, max_candidates=256,
                    brokers=("B1", "B2"), group_cap=8,
                    max_deliver_pairs=max_deliver_pairs, max_notify=max_notify,
                    max_spill=max_spill, spill_capacity=spill_capacity, **kw)
    eng.create_channel(tweets_about_drugs())
    eng.create_channel(tweets_about_crime(1))
    eng.set_user_locations((rng.normal(size=(30, 2)) * 30).astype(np.float32),
                           rng.integers(0, 2, 30))
    eng.subscribe_bulk("TweetsAboutDrugs",
                       rng.integers(0, 50, 200), rng.integers(0, 2, 200))
    eng.ingest(make_tweets(rng, 500, match_drugs=0.3))
    return eng


# ---------------------------------------------------------------------------
# flat pair streams (plans.py)
# ---------------------------------------------------------------------------


def test_flatten_pairs_matches_numpy_reference(rng):
    for _ in range(10):
        C, n, t = (int(rng.integers(1, 5)), int(rng.integers(1, 20)),
                   int(rng.integers(1, 4)))
        rows = rng.integers(0, 999, (C, n, t)).astype(np.int32)
        tgts = rng.integers(0, 99, (C, n, t)).astype(np.int32)
        mask = rng.random((C, n, t)) < 0.4
        cap = int(rng.integers(1, C * n * t + 4))
        s = flatten_pairs_all(jnp.asarray(rows), jnp.asarray(tgts),
                              jnp.asarray(mask), cap)
        flat = mask.reshape(C, -1)
        want_rows = rows.reshape(C, -1)[flat]
        want_ch = np.broadcast_to(np.arange(C)[:, None],
                                  flat.shape)[flat]
        want_tgts = tgts.reshape(C, -1)[flat]
        total = int(mask.sum())
        assert int(s.total) == total
        k = min(total, cap)
        got_valid = np.asarray(s.valid)
        assert got_valid.sum() == k
        np.testing.assert_array_equal(np.asarray(s.rows)[:k], want_rows[:k])
        np.testing.assert_array_equal(np.asarray(s.channels)[:k],
                                      want_ch[:k])
        np.testing.assert_array_equal(np.asarray(s.targets)[:k],
                                      want_tgts[:k])
        assert (np.asarray(s.rows)[k:] == -1).all()      # no tail aliasing


def test_flatten_result_pairs_proportional_to_pending(rng):
    """The compacted stream covers every valid pair of a stacked result once,
    channel-major, regardless of how much padding the shape buckets carry."""
    stacked, _, exp_rows, exp_tgts = random_stacked_broker_result(
        rng, 3, 16, 3, 4, 2)
    total = sum(len(r) for r in exp_rows)
    s = flatten_result_pairs(stacked, max_total=256)
    assert int(s.total) == total
    v = np.asarray(s.valid)
    assert v.sum() == total
    off = 0
    for c in range(3):
        n = len(exp_rows[c])
        np.testing.assert_array_equal(np.asarray(s.rows)[off:off + n],
                                      exp_rows[c])
        np.testing.assert_array_equal(np.asarray(s.targets)[off:off + n],
                                      exp_tgts[c])
        assert (np.asarray(s.channels)[off:off + n] == c).all()
        off += n


def test_flatten_values_truncation(rng):
    vals = rng.integers(0, 100, (2, 10)).astype(np.int32)
    mask = np.ones((2, 10), bool)
    s = flatten_values_all(jnp.asarray(vals), jnp.asarray(mask), 7)
    assert int(s.total) == 20
    np.testing.assert_array_equal(np.asarray(s.values)[:7], vals.ravel()[:7])
    assert (np.asarray(s.channels)[:7] == 0).all()   # first 7 from channel 0


# ---------------------------------------------------------------------------
# fused delivery kernels (broker.py)
# ---------------------------------------------------------------------------


def test_deliver_all_random_invariants(rng):
    """Seeded fuzz of the shared fused-delivery checker (the hypothesis
    variant in test_property.py runs the same checker when installed)."""
    for _ in range(15):
        stacked, group_sids, exp_rows, exp_tgts = random_stacked_broker_result(
            rng, int(rng.integers(1, 4)), int(rng.integers(1, 20)),
            int(rng.integers(1, 4)), int(rng.integers(1, 6)),
            int(rng.integers(1, 4)))
        check_deliver_all_invariants(
            stacked, group_sids, exp_rows, exp_tgts,
            max_pairs=int(rng.integers(1, 12)),
            max_notify=int(rng.integers(1, 16)),
            spill_cap=int(rng.integers(1, 32)))


def test_pack_payloads_all_per_channel_caps(rng):
    """caps (C,) bounds delivery per channel independently of the shared
    buffer size; everything past a cap lands in that channel's spill mask."""
    stacked, group_sids, exp_rows, _ = random_stacked_broker_result(
        rng, 3, 12, 2, 4, 2)
    caps = jnp.asarray([1, 5, 100], jnp.int32)
    d = pack_payloads_all(stacked, jnp.asarray(group_sids), 2, 16, caps=caps)
    for c, cap in enumerate([1, 5, 100]):
        produced = len(exp_rows[c])
        want = min(produced, cap, 16)
        assert int(d.delivered[c]) == want
        assert int(d.spill_mask[c].sum()) == produced - want
        np.testing.assert_array_equal(np.asarray(d.payload[c])[:want, 0],
                                      exp_rows[c][:want])


# ---------------------------------------------------------------------------
# engine: conservation, parity, spill queue, drain
# ---------------------------------------------------------------------------


ALL_FLAGS = [ExecutionFlags(scan_mode=m, aggregation=a, param_pushdown=a)
             for m in ("full", "window", "trad_index", "bad_index")
             for a in (False, True)]


@pytest.mark.parametrize(
    "flags", ALL_FLAGS,
    ids=[f"{f.scan_mode}{'+agg' if f.aggregation else ''}" for f in ALL_FLAGS])
def test_forced_overflow_conservation_and_parity(rng, flags):
    """Under forced overflow: delivered + spilled + dropped == produced per
    stage, on BOTH delivery paths, and the fused path's stats (including the
    one-hot per-broker split) are identical to the per-channel loop's."""
    eng = _overflow_engine(rng)
    fused = eng.execute_all(flags, advance=False, timed=False, deliver=True)
    for name in eng.channels:
        rep = eng.execute_channel(name, flags, advance=False, timed=False,
                                  deliver=True)
        check_delivery_conservation(rep.overflow, rep.num_results,
                                    rep.num_notified)
        check_delivery_conservation(fused[name].overflow,
                                    fused[name].num_results,
                                    fused[name].num_notified)
        assert fused[name].overflow == rep.overflow, name
        assert sum(rep.overflow.delivered_pairs_broker) == \
            rep.overflow.delivered_pairs
        assert rep.overflow.overflow > 0       # caps are tiny: spills happen


def test_drain_redelivers_exactly_once(rng):
    """Every spilled pair/sID is re-delivered exactly once, in spill order:
    the concatenation of drain rounds equals the expected overflow tail of
    the original delivery — no duplicates, no loss — and the queue empties.
    (ring disabled: this exercises the host SpillQueue drain path.)"""
    eng = _overflow_engine(rng, ring_capacity=0)
    flags = ExecutionFlags(scan_mode="window", aggregation=True,
                           param_pushdown=True)
    reps = eng.execute_all(flags, advance=False, timed=False, deliver=True)
    # expected tails from an uncapped re-run of both stages on the results
    want_pairs, want_sids = {}, {}
    for name, rep in reps.items():
        st = eng.channels[name]
        sids_tbl = (jnp.zeros((0,), jnp.int32) if st.spec.join == "spatial"
                    else eng.group_sids_array(name, True))
        buf, dlv, ov = pack_payloads(rep.result, sids_tbl, 2, 1 << 14)
        assert int(ov) == 0
        rows_tgts = np.asarray(buf)[:int(dlv), :2]
        want_pairs[name] = rows_tgts[rep.overflow.delivered_pairs:]
        nbuf, ndlv, nov = fanout_sids(rep.result, sids_tbl, 1 << 15)
        assert int(nov) == 0
        want_sids[name] = np.asarray(nbuf)[rep.overflow.delivered_sids:
                                           int(ndlv)]
        assert len(want_pairs[name]) == rep.overflow.spilled_pairs
        assert len(want_sids[name]) == rep.overflow.spilled_sids
    got_pairs = {n: [] for n in reps}
    got_sids = {n: [] for n in reps}
    rounds = 0
    while eng.spill.pending_pairs() + eng.spill.pending_sids() > 0:
        rounds += 1
        assert rounds < 300
        for name, dr in eng.drain_spilled().items():
            s = dr.stats
            assert s.dropped_pairs == s.dropped_sids == 0
            if dr.payload is not None and s.delivered_pairs:
                got_pairs[name].extend(
                    dr.payload[:s.delivered_pairs, :2].tolist())
            if dr.notify is not None and s.delivered_sids:
                got_sids[name].extend(
                    dr.notify[:s.delivered_sids].tolist())
    for name in reps:
        np.testing.assert_array_equal(np.asarray(got_pairs[name]).reshape(
            -1, 2), want_pairs[name], err_msg=name)
        np.testing.assert_array_equal(np.asarray(got_sids[name]),
                                      want_sids[name], err_msg=name)
    assert not eng.drain_spilled()             # nothing left, no phantom work


def test_spill_queue_capacity_drops_are_counted(rng):
    """A full spill queue degrades to counted drops — conservation still
    holds and only what was actually captured is ever re-delivered."""
    eng = _overflow_engine(rng, spill_capacity=10, ring_capacity=0)
    flags = ExecutionFlags(scan_mode="window")
    reps = eng.execute_all(flags, advance=False, timed=False, deliver=True)
    total_spilled_p = total_spilled_s = 0
    for name, rep in reps.items():
        o = rep.overflow
        check_delivery_conservation(o, rep.num_results, rep.num_notified)
        total_spilled_p += o.spilled_pairs
        total_spilled_s += o.spilled_sids
        assert o.dropped_pairs + o.dropped_sids > 0
    assert total_spilled_p <= 10 and total_spilled_s <= 10
    assert eng.spill.pending_pairs() == total_spilled_p
    assert eng.spill.pending_sids() == total_spilled_s
    redelivered = 0
    while eng.spill.pending_pairs() + eng.spill.pending_sids() > 0:
        for dr in eng.drain_spilled().values():
            redelivered += dr.stats.delivered_pairs + dr.stats.delivered_sids
    assert redelivered == total_spilled_p + total_spilled_s


def test_device_spill_buffer_truncation_counted(rng):
    """max_spill bounds each channel's capture window: overflow past it is
    dropped (counted), never silently lost or aliased — and because the
    windows are per channel, fused capture equals the per-channel path even
    when every channel overflows past the window (no cross-channel
    crowd-out)."""
    eng = _overflow_engine(rng, max_spill=8, ring_capacity=0)
    # a second param channel in the same fused join group: under a shared
    # spill budget its overflow would be crowded out by TweetsAboutDrugs'
    eng.create_channel(most_threatening_tweets())
    eng.subscribe_bulk("MostThreateningTweets",
                       rng.integers(0, 50, 150), rng.integers(0, 2, 150))
    eng.ingest(make_tweets(rng, 300, match_drugs=0.3))
    flags = ExecutionFlags(scan_mode="window")
    fused = eng.execute_all(flags, advance=False, timed=False, deliver=True)
    for name, rep in fused.items():
        o = rep.overflow
        assert o.spilled_pairs <= 8 and o.spilled_sids <= 8
        check_delivery_conservation(o, rep.num_results, rep.num_notified)
        seq = eng.execute_channel(name, flags, advance=False, timed=False,
                                  deliver=True)
        assert seq.overflow == o, name          # parity even past the window
    assert sum(r.overflow.dropped_pairs + r.overflow.dropped_sids
               for r in fused.values()) > 0


def test_drain_mixed_layouts_coherent_payloads(rng):
    """A channel spilled under BOTH layouts drains one lane per round: every
    DrainReport.payload is a single coherent buffer whose delivered prefix
    matches its stats, and both lanes drain to empty with nothing lost."""
    eng = _overflow_engine(rng)
    for agg in (True, False):
        flags = ExecutionFlags(scan_mode="window", aggregation=agg,
                               param_pushdown=agg)
        eng.execute_channel("TweetsAboutDrugs", flags, advance=False,
                            timed=False, deliver=True)
    want = eng.spill.pending_pairs("TweetsAboutDrugs")
    assert len([k for k in eng.spill.pair_keys()
                if k[0] == "TweetsAboutDrugs"]) == 2
    redelivered = 0
    while eng.spill.pending_pairs() > 0:
        for dr in eng.drain_spilled().values():
            if dr.payload is not None:
                # delivered prefix holds real lines, the rest stays zeroed
                n = dr.stats.delivered_pairs
                assert n <= dr.payload.shape[0]
                assert (dr.payload[:n, 3] > 0).all()   # payload_words word
                redelivered += n
    assert redelivered == want


def test_stale_pair_spills_dropped_on_drain(rng):
    """Pair spills index the subscription table they were produced from; a
    re-subscription between spill and drain makes them unroutable — the
    drain counts them dropped instead of re-packing garbage. Raw sID spills
    never go stale and still re-deliver."""
    eng = _overflow_engine(rng)
    flags = ExecutionFlags(scan_mode="window")
    rep = eng.execute_channel("TweetsAboutDrugs", flags, advance=False,
                              timed=False, deliver=True)
    assert rep.overflow.spilled_pairs > 0
    eng.subscribe("TweetsAboutDrugs", 3, "B1")     # version bump
    dropped = delivered_sids = 0
    while eng.spill.pending_pairs("TweetsAboutDrugs") \
            + eng.spill.pending_sids("TweetsAboutDrugs") > 0:
        dr = eng.drain_spilled().get("TweetsAboutDrugs")
        if dr is None:
            break
        assert dr.stats.delivered_pairs == 0       # no stale re-pack
        dropped += dr.stats.dropped_pairs
        delivered_sids += dr.stats.delivered_sids
    assert dropped == rep.overflow.spilled_pairs
    assert delivered_sids == rep.overflow.spilled_sids


def test_spill_queue_unit(rng):
    q = SpillQueue(capacity=5)
    assert q.push_pairs("A", True, np.arange(3), np.arange(3), 0) == 3
    assert q.push_pairs("A", True, np.arange(4), np.arange(4), 0) == 2
    assert q.pending_pairs() == 5 and q.pending_pairs("A") == 5
    rows, tgts, stale = q.pop_pairs("A", True, 4, current_version=0)
    assert stale == 0 and rows.tolist() == [0, 1, 2, 0]
    q._push_front_pairs("A", True, rows[2:], tgts[2:], 0)  # requeue tail
    rows2, _, _ = q.pop_pairs("A", True, 10, current_version=0)
    assert rows2.tolist() == [2, 0, 1]            # front-requeue kept order
    assert q.pending_pairs() == 0
    # stale version accounting
    q.push_pairs("A", True, np.arange(2), np.arange(2), version=7)
    _, _, stale = q.pop_pairs("A", True, 10, current_version=8)
    assert stale == 2
    # sid lane
    assert q.push_sids("A", np.arange(9)) == 5
    assert q.pop_sids("A", 3).tolist() == [0, 1, 2]
    assert q.pending_sids("A") == 2
    q.clear()
    assert q.pending_pairs() + q.pending_sids() == 0


def test_deliver_false_leaves_no_trace(rng):
    eng = _overflow_engine(rng)
    flags = ExecutionFlags(scan_mode="window")
    reps = eng.execute_all(flags, advance=False, timed=False)
    assert all(r.overflow is None for r in reps.values())
    assert eng.spill.pending_pairs() + eng.spill.pending_sids() == 0
    assert not eng.drain_spilled()


# ---------------------------------------------------------------------------
# send stage: dense notify-buffer resolution vs the kept search
# ---------------------------------------------------------------------------


def _send_case(rng, C, identity, n_rows=6, max_t=3, n_groups=5, cap=4):
    """Random stacked result whose pairs include zero-member ones: invalid
    pairs, empty groups (group tables) and negative targets (identity
    fanout). Returns the result, the sID table and each channel's member
    stream in delivery order."""
    import jax.numpy as jnp
    from repro.core.plans import ChannelResult
    valid = rng.random((C, n_rows, max_t)) < 0.6
    rows = rng.integers(0, 1000, (C, n_rows, max_t)).astype(np.int32)
    if identity:
        tgts = rng.integers(-1, 40, (C, n_rows, max_t)).astype(np.int32)
        group_sids = np.zeros((C, 0), np.int32)
    else:
        tgts = rng.integers(0, n_groups, (C, n_rows, max_t)).astype(np.int32)
        counts = rng.integers(0, cap + 1, (C, n_groups))
        counts[:, 0] = 0                       # at least one empty group
        group_sids = np.full((C, n_groups, cap), -1, np.int32)
        for c in range(C):
            for g in range(n_groups):
                group_sids[c, g, :counts[c, g]] = rng.integers(
                    0, 10000, counts[c, g])
    streams = []
    for c in range(C):
        v, t = valid[c].ravel(), tgts[c].ravel()
        if identity:
            streams.append(t[v & (t >= 0)])
        else:
            m = group_sids[c][t[v]]
            streams.append(m[m >= 0])
    z = jnp.zeros((C,), jnp.int32)
    res = ChannelResult(jnp.asarray(rows), jnp.asarray(tgts),
                        jnp.asarray(valid), jnp.asarray(rows[:, :, 0]),
                        jnp.asarray(valid[:, :, 0]), z, z, z,
                        jnp.zeros((C, 1), jnp.int32),
                        jnp.zeros((C, 1), jnp.int32))
    return res, group_sids, streams


SEND_CASES = {   # channels, resident ring sIDs (max), notify size, caps
    "plain": (1, 0, "fits", False),
    "resident": (2, 4, "fits", False),
    "capped": (3, 0, "fits", True),
    "resident_capped": (2, 6, "fits", True),
    "past_q": (2, 0, "short", False),
    "resident_past_q": (3, 3, "short", True),
}


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("identity", [False, True], ids=["groups", "identity"])
@pytest.mark.parametrize("case", sorted(SEND_CASES))
def test_dense_send_matches_search(case, identity, seed):
    """The send stage's dense resolution (a scatter of run starts and a
    prefix max) gives every rank the owner pair, in-pair offset and sID the
    kept binary search (``_member_lookup``) gives it, and the whole notify
    buffer — resident ring sIDs first, caps and ranks past the buffer
    included — equals the member stream expanded in numpy."""
    import jax.numpy as jnp
    from repro.core import broker
    C, shift_max, q, capped = SEND_CASES[case]
    rng = np.random.default_rng(1000 * seed + sorted(SEND_CASES).index(case))
    res, group_sids, streams = _send_case(rng, C, identity)
    gs = jnp.asarray(group_sids)
    longest = max(len(s) for s in streams) + shift_max
    Q = max(1, longest // 2) if q == "short" else longest + 5
    if q == "short":
        assert longest > Q                     # produced past the buffer
    shift = rng.integers(0, shift_max + 1, C).astype(np.int32)
    shift[0] = shift_max
    caps = None
    if capped:
        caps = np.array([max(0, len(s) + int(r) - 2) for s, r
                         in zip(streams, shift)], np.int32)

    valid2 = res.pair_valid.reshape(C, -1)
    tgt2 = res.pair_targets.reshape(C, -1)
    members = broker._member_counts(gs, valid2, tgt2)
    cumm = jnp.cumsum(members, axis=1)
    p_d, j_d = broker._dense_ranks(members, cumm, jnp.asarray(shift), Q)
    k = np.arange(Q)[None, :]
    kf = k - shift[:, None]
    ok = (kf >= 0) & (kf < np.asarray(cumm[:, -1])[:, None])
    p_s, j_s = broker._search_ranks(members, cumm,
                                    jnp.asarray(np.maximum(kf, 0)))
    np.testing.assert_array_equal(np.asarray(p_d)[ok], np.asarray(p_s)[ok])
    np.testing.assert_array_equal(np.asarray(j_d)[ok], np.asarray(j_s)[ok])
    want = broker._member_lookup(gs, tgt2, members, cumm,
                                 jnp.asarray(np.maximum(kf, 0)),
                                 jnp.asarray(ok))
    got = jnp.where(jnp.asarray(ok),
                    broker._pair_member(gs, tgt2, p_d, j_d), -1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    W = max(shift_max, 1)
    ring_vals = np.full((C, W), -1, np.int32)
    for c in range(C):
        ring_vals[c, :shift[c]] = rng.integers(20000, 30000, shift[c])
    fan, _ = broker._fanout_parts(
        res, gs, Q, None if caps is None else jnp.asarray(caps),
        resident=(jnp.asarray(ring_vals), jnp.asarray(shift)))
    for c in range(C):
        full = np.concatenate([ring_vals[c, :shift[c]], streams[c]])
        cap_c = Q if caps is None else min(int(caps[c]), Q)
        n = min(len(full), cap_c)
        exp = np.full(Q, -1, np.int32)
        exp[:n] = full[:n]
        assert int(fan.produced[c]) == len(full)
        assert int(fan.delivered[c]) == n
        np.testing.assert_array_equal(np.asarray(fan.notify[c]), exp)


@pytest.mark.parametrize("with_ring", [False, True], ids=["plain", "ring"])
def test_send_stage_compiles_without_search(rng, with_ring):
    """No ``while`` (the binary search's loop) carries the ``bad.send``
    scope in the compiled delivery program, with or without a ring; the
    sparse lookups of the overflow tails keep their search under
    ``bad.ring``."""
    import re

    import jax
    from repro.core import broker
    stacked, group_sids, _, _ = random_stacked_broker_result(rng, 2, 8, 4,
                                                             6, 3)
    ring = broker.empty_ring(2, 16) if with_ring else None
    epochs = jnp.zeros((2,), jnp.int32) if with_ring else None

    def deliver(res, sids):
        return broker.deliver_all(res, sids, 2, 16, 32, 8, ring=ring,
                                  epochs=epochs)

    text = jax.jit(deliver).lower(stacked,
                                  jnp.asarray(group_sids)).compile().as_text()
    loops = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines() if re.search(r"\bwhile\(", line)]
    assert not [name for name in loops if "/bad.send/" in name], loops
    assert [name for name in loops if "/bad.ring/" in name], loops
