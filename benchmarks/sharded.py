"""Sharded-engine scaling: delivered-notification throughput, N=4 vs N=1.

The mesh partitions the subscription population, so aggregate delivery
capacity (per-tick delivery caps, retry-ring slots) scales with the shard
count while per-DEVICE resources stay fixed. This suite drives both engines
through the same seeded workload in a sustained-overflow regime — produced
notifications per tick exceed a single device's delivery caps several times
over — then lets each engine drain to empty. The single-device engine needs
~4x the effective ticks (each re-paying the join) and falls back to host
spill once its one ring fills; the 4-shard engine absorbs the same stream
with per-shard rings and 4x the per-tick delivery budget.

Metric: delivered subscription notifications (sIDs) per second over the
whole stream including the drain tail — partition-independent content, so
the suite asserts both engines delivered the IDENTICAL total with zero
drops before quoting a ratio.

Sizing note: this suite runs the SAME size under ``--smoke`` — the measured
quantity is a capacity ratio, which is only meaningful when the
shard-divisible join work (candidates x groups) dominates the fixed
per-engine-call dispatch cost. Shrinking the population pushes the regime
to dispatch-bound, where an N-shard engine on one CPU core pays N
dispatches per tick and the ratio collapses to noise. 32k subscriptions at
group_cap=2 (16k groups) is the smallest validated join-dominant point.

Device-count mechanics: both engines run in THIS process over
``jax.devices()`` — the 1-shard engine on ``devices[0]``, the 4-shard engine
on ``devices[:4]``. A runtime with fewer than 4 devices fails the suite; on
CPU, start the process with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
(the forced count is fixed when JAX initializes). Nothing is spawned: a child
process cannot reach a chip that its parent already holds.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks import common
from repro.core import records as R
from repro.core.channel import tweets_about_drugs
from repro.core.plans import ExecutionFlags
from repro.core.sharded import ShardedBADEngine
from repro.data.synthetic import drug_tweak, tweet_batch


def run(rng) -> None:
    if jax.device_count() < 4:
        raise RuntimeError(
            f"sharded_scaling needs 4 devices, the runtime has "
            f"{jax.device_count()}; on CPU start the process with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=4")
    n_subs, ingest, ticks = 32000, 128, 6    # same under smoke; see above
    rows = {s: _measure(s, n_subs, ingest, ticks) for s in (1, 4)}
    r1, r4 = rows[1], rows[4]
    # the ratio is only meaningful over identical content, delivered exactly
    assert r1["dropped"] == r4["dropped"] == 0, (r1, r4)
    assert r1["delivered"] == r4["delivered"], (r1, r4)
    rate1 = r1["delivered"] / r1["wall"]
    rate4 = r4["delivered"] / r4["wall"]
    common.emit("sharded/scaling_n1/rate", r1["wall"],
                f"{rate1:.0f} notifications/s over {r1['ticks']} ticks "
                f"(1 shard, drain included)")
    common.emit("sharded/scaling_n4/speedup", r4["wall"],
                f"x{rate4 / rate1:.2f} delivered-notification throughput vs "
                f"1 shard ({rate4:.0f}/s, {r4['ticks']} ticks, fixed "
                f"per-device caps)")
    # dispatch-to-materialize latency of one fused tick across all shards
    # (the window the pipelined runtime overlaps with control-plane work)
    for s in (1, 4):
        common.emit(f"sharded/scaling_n{s}/tick_latency", rows[s]["p50"],
                    f"p50={rows[s]['p50'] * 1e3:.1f}ms;"
                    f"p99={rows[s]['p99'] * 1e3:.1f}ms "
                    f"dispatch-to-materialize, {s} shard(s)")


def _measure(num_shards: int, n_subs: int, ingest: int, ticks: int) -> dict:
    """One engine on ``jax.devices()[:num_shards]``, one measurement."""

    def make_tweets(rng, n, t0):
        batch = tweet_batch(rng, n, t0)
        fields = drug_tweak(np.asarray(batch.fields).copy(), rng, 0.1)
        return R.RecordBatch.from_numpy(fields, np.asarray(batch.location))

    flags = ExecutionFlags(scan_mode="window", aggregation=True,
                           param_pushdown=True)
    rng = np.random.default_rng(common.SEED)
    eng = ShardedBADEngine(
        num_shards=num_shards,
        dataset_capacity=1 << 15, index_capacity=1 << 12,
        max_window=1 << 12, max_candidates=1 << 11,
        brokers=("B1", "B2"), group_cap=2,    # many small groups: the join
        # grid (candidates x groups) is the shard-divisible cost
        max_deliver_pairs=128, max_notify=1024,    # per DEVICE, fixed
        ring_capacity=1 << 14, max_spill=1 << 14,
        spill_capacity=1 << 19)
    eng.create_channel(tweets_about_drugs())
    eng.subscribe_bulk("TweetsAboutDrugs", rng.integers(0, 50, n_subs),
                       rng.integers(0, 2, n_subs))
    # warmup: trace/compile + two steady ticks, then settle so the timed
    # window starts from an empty ring on every shard
    for w in range(2):
        eng.ingest(make_tweets(rng, ingest, t0=100 * (w + 1)))
        eng.execute_all(flags, timed=False, deliver=True)
    for _ in range(5000):
        if eng.ring_pending_pairs() + eng.ring_pending_sids() == 0:
            break
        eng.execute_all(flags, timed=False, deliver=True)
    while eng.spill.pending_pairs() + eng.spill.pending_sids() > 0:
        eng.drain_spilled()

    delivered = dropped = ticks_run = 0

    def account(stats):
        nonlocal delivered, dropped
        delivered += stats.delivered_sids
        dropped += stats.dropped_pairs + stats.dropped_sids

    t0 = time.perf_counter()
    lat = []     # per-tick dispatch-to-materialize seconds
    for tick in range(ticks):
        eng.ingest(make_tweets(rng, ingest, t0=1000 * (tick + 3)))
        # dispatch/sync split so the measured latency is the one the
        # pipelined runtime hides: all shards enqueue before any blocks
        pend = eng.dispatch_all(flags, timed=False, deliver=True)
        reps = pend.sync()
        lat.append(pend.latency_s)
        ticks_run += 1
        for rep in reps.values():
            account(rep.overflow)
    # drain to empty: the capacity-bound engine keeps paying join ticks
    for _ in range(10000):
        if eng.ring_pending_pairs() + eng.ring_pending_sids() == 0:
            break
        reps = eng.execute_all(flags, timed=False, deliver=True)
        ticks_run += 1
        for rep in reps.values():
            account(rep.overflow)
    while eng.spill.pending_pairs() + eng.spill.pending_sids() > 0:
        for dr in eng.drain_spilled().values():
            account(dr.stats)
    wall = time.perf_counter() - t0
    p50, p99 = (float(np.percentile(lat, q)) for q in (50, 99))
    return dict(delivered=delivered, dropped=dropped, wall=wall,
                ticks=ticks_run, p50=p50, p99=p99)
