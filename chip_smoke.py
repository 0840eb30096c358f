"""Chip smoke test: the BAD engine's main path on a TPU at the paper's §5.1 scale.

    python chip_smoke.py               # one chip: oracle, pallas, compact_pallas
    python chip_smoke.py --four-chips  # ShardedBADEngine on 4 chips vs 1 shard

The one-chip run preloads the §5.1 deployment (``configs/bad_default``: 2M
EnrichedTweets, 1M subscribers over 50 states with the population skew, 4
brokers, 40 KB frames) through ``BADEngine.ingest``, then drives delivering
ticks through ``TickPipeline`` once per backend family a user can select.
Every tick's ``num_results`` / ``num_notified`` must equal a plain host
numpy evaluation of the same channel semantics, every tick must conserve
(delivered + spilled + dropped == produced), every delivered (row, sID) must
belong to the reference set, and after a final flush and drain every backend
must have delivered exactly the reference notification multiset.

The four-chip run drives the same deployment with subscription churn through
``churn.run_ticks`` on a 4-shard ``ShardedBADEngine`` with cross-shard
routing and on a 1-shard engine, and checks sID-multiset parity, the routed
buffers against ``collectives.shuffle_notify_ref``, and that every shard's
state sits on its own chip.

The script refuses to run without a TPU. Its last stdout line is one JSON
object, ``{"ok": true, "device": {"platform", "kind", "count"}}``; any
failed check raises, so nothing is printed there and the exit code is not 0.
The times it prints are smoke timings of this run, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import json
import pathlib
import sys
import tempfile
import time
from typing import Dict, List

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import bad_default  # noqa: E402
from repro.core import predicates as P  # noqa: E402
from repro.core import records as R  # noqa: E402
from repro.core import subscriptions as subs  # noqa: E402
from repro.core.broker import payload_notifications  # noqa: E402
from repro.core.channel import tweets_about_crime, tweets_about_drugs  # noqa: E402
from repro.core.engine import BADEngine  # noqa: E402
from repro.core.plans import ChannelPlan  # noqa: E402
from repro.core.runtime import TickPipeline  # noqa: E402
from repro.data.synthetic import subscriptions_by_population, tweet_batch  # noqa: E402
from repro.kernels import on_tpu  # noqa: E402

DRUGS = tweets_about_drugs()
# all five predicates of Fig. 15 (0.5% selectivity): the spatial pair volume
# then fits the same delivery buffers as the 40 KB drug frames
CRIME = tweets_about_crime(5)
BACKENDS = ("oracle", "pallas", "compact_pallas")
SID_BITS = 21          # (row, sID) keys: row << SID_BITS | sID


@dataclasses.dataclass(frozen=True)
class Deployment:
    """Sizes of one smoke run. ``published()`` is the §5.1 deployment;
    ``tiny()`` keeps every shape and cuts every count, for CPU rehearsals."""

    preload: int               # records ingested before the channels exist
    preload_batch: int         # fixed ingest batch: one ingest trace
    tick_records: int          # records ingested per tick
    ticks: int
    subscribers: int           # TweetsAboutDrugs subscriptions
    states: int
    brokers: int
    frame_bytes: int
    users: int                 # located users; all in the crime cohort
    dataset_capacity: int
    max_deliver_pairs: int
    max_notify: int
    records_per_s: int
    period_s: int
    churn_per_tick: int        # drug adds and removes per tick (--four-chips)
    user_churn_per_tick: int   # crime cohort leaves and joins per tick
    seed: int = 0

    @property
    def total_records(self) -> int:
        return self.preload + self.ticks * self.tick_records

    def engine_kwargs(self) -> dict:
        return dict(dataset_capacity=self.dataset_capacity,
                    frame_bytes=self.frame_bytes,
                    brokers=tuple(f"Broker{i + 1}"
                                  for i in range(self.brokers)),
                    max_deliver_pairs=self.max_deliver_pairs,
                    max_notify=self.max_notify)


def published() -> Deployment:
    w = bad_default.get_config()
    return Deployment(
        preload=w.preload_records, preload_batch=16_000,
        tick_records=16_384, ticks=8, subscribers=w.num_subscribers,
        states=w.num_states, brokers=w.num_brokers,
        frame_bytes=w.frame_bytes, users=16_384,
        dataset_capacity=1 << 22,
        # per tick: <= 12.6k spatial pairs, ~800 drug frames and <= 6.6M
        # drug sIDs (the reference at seed 0)
        max_deliver_pairs=1 << 14, max_notify=1 << 23,
        records_per_s=w.tweets_per_second, period_s=w.period_s,
        churn_per_tick=2_048, user_churn_per_tick=256)


def tiny() -> Deployment:
    return Deployment(
        preload=2_048, preload_batch=512, tick_records=1_024, ticks=4,
        subscribers=4_000, states=50, brokers=4, frame_bytes=256,
        users=2_048, dataset_capacity=1 << 13,
        max_deliver_pairs=1 << 10, max_notify=1 << 15,
        records_per_s=2_000, period_s=600,
        churn_per_tick=128, user_churn_per_tick=32)


# ---------------------------------------------------------------------------
# seeded data and the plain host reference
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Data:
    fields: np.ndarray         # (total_records, F) int32, row id == index
    locs: np.ndarray           # (total_records, 2) f32
    sub_params: np.ndarray     # (subscribers,) int32; sID == index
    sub_brokers: np.ndarray
    user_locs: np.ndarray      # (users, 2) f32; uid == index
    user_brokers: np.ndarray


def make_data(dep: Deployment) -> Data:
    rng = np.random.default_rng(dep.seed)
    batch = tweet_batch(rng, dep.total_records, t0=0,
                        rate_per_s=dep.records_per_s)
    params, brokers = subscriptions_by_population(rng, dep.subscribers,
                                                  dep.brokers)
    user_locs = rng.uniform(-100, 100, (dep.users, 2)).astype(np.float32)
    user_brokers = rng.integers(0, dep.brokers, dep.users).astype(np.int32)
    return Data(np.asarray(batch.fields), np.asarray(batch.location),
                params % dep.states, brokers, user_locs, user_brokers)


def tick_rows(dep: Deployment, t: int) -> slice:
    start = dep.preload + t * dep.tick_records
    return slice(start, start + dep.tick_records)


_NP_OPS = {P.EQ: np.equal, P.NE: np.not_equal, P.LT: np.less,
           P.LE: np.less_equal, P.GT: np.greater, P.GE: np.greater_equal}


def _matches(fields: np.ndarray, spec) -> np.ndarray:
    """The channel's fixed conjunction, evaluated with numpy."""
    ok = np.ones(fields.shape[0], bool)
    for p in spec.fixed_preds:
        ok &= _NP_OPS[p.op](fields[:, p.field], p.value)
    return ok


@dataclasses.dataclass
class Reference:
    """Per-tick expected counts and the expected notification multisets,
    computed with numpy from the channel definitions alone."""

    results: Dict[str, List[int]]
    notified: Dict[str, List[int]]
    drug_match: np.ndarray         # (total_records,) bool, tick rows only
    sid_counts: Dict[str, np.ndarray]   # expected notifications per sID
    crime_keys: np.ndarray         # sorted (row, uid) keys


def reference(dep: Deployment, data: Data) -> Reference:
    cap = subs.cap_from_frame_bytes(dep.frame_bytes)
    per = np.zeros((dep.states, dep.brokers), np.int64)
    np.add.at(per, (data.sub_params, data.sub_brokers), 1)
    subs_of_state = per.sum(axis=1)
    # Algorithm 1: each (state, broker) key chops into ceil(n / cap) groups
    groups_of_state = np.ceil(per / cap).astype(np.int64).sum(axis=1)
    drug_match = np.zeros(dep.total_records, bool)
    rows_of_state = np.zeros(dep.states, np.int64)
    crime_uid_counts = np.zeros(dep.users, np.int64)
    results = {DRUGS.name: [], CRIME.name: []}
    notified = {DRUGS.name: [], CRIME.name: []}
    crime_keys = []
    r2 = np.float32(CRIME.spatial_radius) ** 2
    for t in range(dep.ticks):
        sl = tick_rows(dep, t)
        f = data.fields[sl]
        m = _matches(f, DRUGS)
        drug_match[sl] = m
        st = f[m, DRUGS.param_field]
        np.add.at(rows_of_state, st, 1)
        results[DRUGS.name].append(int(groups_of_state[st].sum()))
        notified[DRUGS.name].append(int(subs_of_state[st].sum()))
        rows = np.flatnonzero(_matches(f, CRIME)) + sl.start
        d = data.locs[rows][:, None, :] - data.user_locs[None, :, :]
        hit = (d[..., 0] * d[..., 0] + d[..., 1] * d[..., 1]) < r2
        r_idx, uids = np.nonzero(hit)
        crime_keys.append((rows[r_idx].astype(np.int64) << SID_BITS) | uids)
        np.add.at(crime_uid_counts, uids, 1)
        results[CRIME.name].append(int(hit.sum()))
        notified[CRIME.name].append(int(hit.sum()))
    return Reference(
        results, notified, drug_match,
        {DRUGS.name: rows_of_state[data.sub_params],
         CRIME.name: crime_uid_counts},
        np.sort(np.concatenate(crime_keys)))


# ---------------------------------------------------------------------------
# one backend on one chip
# ---------------------------------------------------------------------------


class Delivered:
    """Everything one engine delivered, per channel: (row, sID) keys from
    the convert stage's wire lines and sIDs from the send stage's buffer."""

    def __init__(self, payload_words: int):
        self.pw = payload_words
        self.keys = collections.defaultdict(list)
        self.sids = collections.defaultdict(list)

    def add(self, channel: str, payload, n_pairs: int, notify,
            n_sids: int) -> None:
        if payload is not None and n_pairs:
            rs = payload_notifications(payload, n_pairs, self.pw)
            self.keys[channel].append((rs[:, 0] << SID_BITS) | rs[:, 1])
        if notify is not None and n_sids:
            self.sids[channel].append(np.asarray(notify)[:n_sids])

    def keys_of(self, channel: str) -> np.ndarray:
        return np.concatenate(self.keys[channel] or [np.zeros(0, np.int64)])

    def sid_counts(self, channel: str, n: int = 0) -> np.ndarray:
        got = np.concatenate(self.sids[channel] or [np.zeros(0, np.int32)])
        return np.bincount(got, minlength=n)


def same_counts(a: np.ndarray, b: np.ndarray) -> bool:
    """Equal multisets, given as bincounts of possibly different lengths."""
    n = max(a.size, b.size)
    return np.array_equal(np.pad(a, (0, n - a.size)),
                          np.pad(b, (0, n - b.size)))


def device_arrays(obj) -> List[jax.Array]:
    """The device arrays held by a pytree or by a cache dataclass."""
    if dataclasses.is_dataclass(obj):
        obj = list(vars(obj).values())
    return [x for x in jax.tree_util.tree_leaves(obj)
            if isinstance(x, jax.Array)]


def check_conservation(o, num_results: int, num_notified: int) -> None:
    assert (o.delivered_pairs + o.spilled_pairs + o.dropped_pairs
            == num_results + o.retried_pairs), o
    assert (o.delivered_sids + o.spilled_sids + o.dropped_sids
            == num_notified + o.retried_sids), o


def drain_to_empty(eng, delivered: Delivered) -> None:
    while eng.spill.pending_pairs() + eng.spill.pending_sids() > 0:
        for name, rep in eng.drain_spilled().items():
            name = name.split("@")[0]
            assert rep.stats.dropped_pairs == rep.stats.dropped_sids == 0, \
                (name, rep.stats)
            delivered.add(name, rep.payload, rep.stats.delivered_pairs,
                          rep.notify, rep.stats.delivered_sids)


def load_engine(dep: Deployment, data: Data, use_pallas: bool) -> BADEngine:
    """The deployment's engine: preload, then channels and subscriptions
    (BAD indexes serve records ingested after their channel exists)."""
    eng = BADEngine(use_pallas=use_pallas, **dep.engine_kwargs())
    eng.debug_delivery_buffers = True
    for i in range(0, dep.preload, dep.preload_batch):
        j = min(i + dep.preload_batch, dep.preload)
        eng.ingest(R.RecordBatch.from_numpy(data.fields[i:j], data.locs[i:j]))
    eng.set_user_locations(data.user_locs, data.user_brokers)
    eng.create_channel(DRUGS)
    eng.create_channel(CRIME)
    eng.subscribe_bulk(DRUGS.name, data.sub_params, data.sub_brokers)
    eng.subscribe_users(CRIME.name, np.arange(dep.users, dtype=np.int32))
    return eng


def run_backend(dep: Deployment, data: Data, ref: Reference,
                backend: str) -> dict:
    """Tick the deployment under one backend through ``TickPipeline`` and
    check every tick against the reference. Returns the delivered
    notification counts per sID, smoke timings and what was compiled."""
    with watch_compiles() as compiles:
        out = _run_backend(dep, data, ref, backend)
    # the programs that ran the ticks: ingest (BAD index maintenance) and
    # the fused plan-group call. A pallas-family backend on a TPU must have
    # run its kernels compiled (Mosaic), never through the interpreter.
    want = backend != "oracle" and on_tpu()
    for program in ("jit_ingest_step", "jit_run"):
        assert compiles.mosaic.get(program) == want, \
            (backend, program, compiles.mosaic.get(program), want)
    out["compiles"] = compiles
    return out


def _run_backend(dep: Deployment, data: Data, ref: Reference,
                 backend: str) -> dict:
    t0 = time.perf_counter()
    eng = load_engine(dep, data, use_pallas=backend != "oracle")
    plan = ChannelPlan(scan_mode="bad_index", aggregation=True,
                       param_pushdown=True, backend=backend)
    for name in (DRUGS.name, CRIME.name):
        eng.set_plan(name, plan)
    jax.block_until_ready(eng.dataset.fields)
    setup_s = time.perf_counter() - t0
    delivered = Delivered(eng.deliver_payload_words)
    seen = set()

    def record(t: int, reports: Dict) -> None:
        seen.add(t)
        for name, rep in reports.items():
            assert rep.num_results == ref.results[name][t], \
                (backend, name, t, rep.num_results, ref.results[name][t])
            assert rep.num_notified == ref.notified[name][t], \
                (backend, name, t, rep.num_notified, ref.notified[name][t])
            o = rep.overflow
            check_conservation(o, rep.num_results, rep.num_notified)
            assert o.dropped_pairs == o.dropped_sids == 0, (name, t, o)
            delivered.add(name, rep.payload, o.delivered_pairs, rep.notify,
                          o.delivered_sids)

    pipe = TickPipeline(eng, depth=2)
    tick_s = []
    for t in range(dep.ticks):
        sl = tick_rows(dep, t)
        t1 = time.perf_counter()
        eng.ingest(R.RecordBatch.from_numpy(data.fields[sl], data.locs[sl]))
        ready = pipe.step(None, deliver=True)
        tick_s.append(time.perf_counter() - t1)
        for tick_no, reports in ready:
            record(tick_no, reports)
        if pipe.drain_due():
            drain_to_empty(eng, delivered)
    for tick_no, reports in pipe.flush():
        record(tick_no, reports)
    eng.flush_rings()
    drain_to_empty(eng, delivered)
    assert seen == set(range(dep.ticks)), seen

    # the delivered multisets ARE the reference multisets
    drug = delivered.keys_of(DRUGS.name)
    rows, sids = drug >> SID_BITS, drug & ((1 << SID_BITS) - 1)
    ok = ref.drug_match[rows] & (
        data.fields[rows, DRUGS.param_field] == data.sub_params[sids])
    assert ok.all(), (backend, int((~ok).sum()), "drug pairs off reference")
    assert np.unique(drug).size == drug.size == sum(
        ref.notified[DRUGS.name]), (backend, drug.size)
    crime = np.sort(delivered.keys_of(CRIME.name))
    assert np.array_equal(crime, ref.crime_keys), (backend, crime.size,
                                                   ref.crime_keys.size)
    counts = {DRUGS.name: delivered.sid_counts(DRUGS.name, dep.subscribers),
              CRIME.name: delivered.sid_counts(CRIME.name, dep.users)}
    for name, want in ref.sid_counts.items():
        assert np.array_equal(counts[name], want), (backend, name)
    steady = tick_s[2:] or tick_s
    return dict(counts=counts, setup_s=setup_s, first_tick_s=tick_s[0],
                steady_tick_s=float(np.median(steady)),
                pairs=drug.size + crime.size)


@dataclasses.dataclass
class CompileLog:
    """What JAX compiled inside one ``watch_compiles`` block."""

    seconds: float = 0.0       # backend compiles, persistent-cache reads incl.
    cache: collections.Counter = dataclasses.field(
        default_factory=collections.Counter)
    # program name -> whether its StableHLO calls a Mosaic kernel
    mosaic: Dict[str, bool] = dataclasses.field(default_factory=dict)


@contextlib.contextmanager
def watch_compiles():
    """Record compile time, persistent-cache hits/misses and, from the
    StableHLO JAX dumps for every program it compiles, which programs call
    a compiled Pallas kernel (``tpu_custom_call``; interpret mode lowers a
    kernel to plain XLA ops instead)."""
    log = CompileLog()

    def on_duration(event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            log.seconds += secs

    def on_event(event: str, **_kw) -> None:
        if event.startswith("/jax/compilation_cache/cache_"):
            log.cache[event.rsplit("/", 1)[1]] += 1

    dump_was = jax.config.values["jax_dump_ir_to"]
    with tempfile.TemporaryDirectory() as d:
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        jax.config.update("jax_dump_ir_to", d)
        try:
            yield log
        finally:
            jax.config.update("jax_dump_ir_to", dump_was)
            jax.monitoring.unregister_event_duration_listener(on_duration)
            jax.monitoring.unregister_event_listener(on_event)
        # jax_ir0007_jit_run_compile.mlir -> jit_run
        for f in pathlib.Path(d).glob("*_compile.mlir"):
            name = f.stem.split("_", 2)[2].removesuffix("_compile")
            log.mosaic[name] = (log.mosaic.get(name, False)
                                or "tpu_custom_call" in f.read_text())


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def peak_bytes(device) -> str:
    stats = device.memory_stats()
    return str(stats["peak_bytes_in_use"]) if stats else "not reported"


def run_one_chip(dep: Deployment) -> collections.Counter:
    """The one-chip phases; returns the persistent-cache hits/misses."""
    period = dep.records_per_s * dep.period_s
    cut = dep.ticks * dep.tick_records
    print(f"deployment: {dep.preload} preloaded records, {dep.subscribers} "
          f"subscribers over {dep.states} states, {dep.brokers} brokers, "
          f"{dep.frame_bytes} B frames, {dep.users}-user crime cohort")
    print(f"period cut: {dep.ticks} ticks x {dep.tick_records} records = "
          f"{cut} records ({cut / dep.records_per_s:.1f} s at "
          f"{dep.records_per_s} records/s) of the {dep.period_s} s period "
          f"({period} records), which one window cannot scan yet")
    data = make_data(dep)
    ref = reference(dep, data)
    print(f"reference: {sum(ref.notified[DRUGS.name])} drug and "
          f"{sum(ref.notified[CRIME.name])} crime notifications over "
          f"{dep.ticks} ticks")
    runs = {}
    cache: collections.Counter = collections.Counter()
    for backend in BACKENDS:
        res = run_backend(dep, data, ref, backend)
        c = res["compiles"]
        cache += c.cache
        kernels = sorted(p for p, m in c.mosaic.items() if m)
        print(f"{backend}: every tick matches the numpy reference and "
              f"conserves; {res['pairs']} delivered (row, sID) pairs == "
              f"reference; programs calling compiled kernels: {kernels}; "
              f"compile {c.seconds:.2f} s (cache hits={c.cache['cache_hits']}"
              f" misses={c.cache['cache_misses']}); smoke timing (not a "
              f"metric): setup {res['setup_s']:.2f} s, first tick "
              f"{res['first_tick_s']:.2f} s, steady tick "
              f"{res['steady_tick_s']:.3f} s", flush=True)
        runs[backend] = res
    first = runs[BACKENDS[0]]["counts"]
    for backend in BACKENDS[1:]:
        for name, want in first.items():
            assert same_counts(runs[backend]["counts"][name], want), \
                (backend, name)
    print(f"parity: {', '.join(BACKENDS)} delivered the same sID multiset "
          f"per channel")
    return cache


def run_four_chips(dep: Deployment) -> None:
    """4-shard engine with cross-shard routing vs a 1-shard engine on the
    same seeded stream with churn through ``run_ticks``."""
    from repro.core.churn import ChurnWorkload, run_ticks
    from repro.core.sharded import ShardedBADEngine
    from repro.distributed import collectives, partition
    devices = jax.devices()
    assert len(devices) >= 4, f"--four-chips needs 4 devices, have {devices}"
    data = make_data(dep)
    plan = ChannelPlan(scan_mode="bad_index", aggregation=True,
                       param_pushdown=True, backend="oracle")
    results = {}
    for n in (4, 1):
        t0 = time.perf_counter()
        eng = ShardedBADEngine(num_shards=n, route_cross_shard=n > 1,
                               **dep.engine_kwargs())
        eng.debug_delivery_buffers = True
        for i in range(0, dep.preload, dep.preload_batch):
            j = min(i + dep.preload_batch, dep.preload)
            eng.ingest(R.RecordBatch.from_numpy(data.fields[i:j],
                                                data.locs[i:j]))
        eng.set_user_locations(data.user_locs, data.user_brokers)
        eng.create_channel(DRUGS)
        eng.create_channel(CRIME)      # cohort: every located user
        live = {DRUGS.name: eng.subscribe_bulk(DRUGS.name, data.sub_params,
                                               data.sub_brokers)}
        for name in (DRUGS.name, CRIME.name):
            eng.set_plan(name, plan)
        delivered = Delivered(eng.shards[0].deliver_payload_words)
        routed = [0]

        def on_tick(t, reports, eng=eng, delivered=delivered, routed=routed):
            for name, rep in reports.items():
                check_conservation(rep.overflow, rep.num_results,
                                   rep.num_notified)
                for r in rep.per_shard:
                    delivered.add(name, None, 0, r.notify,
                                  r.overflow.delivered_sids)
                if eng.num_shards == 1:
                    continue
                sids = np.stack([np.asarray(r.notify) for r in rep.per_shard])
                owners = np.full(sids.shape, -1, np.int32)
                live_ = sids >= 0
                bids = (data.user_brokers[sids[live_]] if name == CRIME.name
                        else eng._reg[name].brokers[sids[live_]])
                owners[live_] = partition.broker_owner(bids, eng.num_shards)
                want = collectives.shuffle_notify_ref(sids, owners,
                                                      eng.num_shards)
                assert np.array_equal(rep.routed, want), (name, t)
                routed[0] += int((want >= 0).sum())

        def on_drain(reports, delivered=delivered):
            for key, rep in reports.items():
                delivered.add(key.split("@")[0], None, 0, rep.notify,
                              rep.stats.delivered_sids)

        batches = iter(range(dep.ticks))

        def make_batch(_rng, _n, _t0):
            sl = tick_rows(dep, next(batches))
            return R.RecordBatch.from_numpy(data.fields[sl], data.locs[sl])

        churn = [ChurnWorkload(DRUGS.name, adds_per_tick=dep.churn_per_tick,
                               removes_per_tick=dep.churn_per_tick,
                               param_domain=dep.states,
                               num_brokers=dep.brokers,
                               user_channel=CRIME.name,
                               user_churn_per_tick=dep.user_churn_per_tick)]
        rep = run_ticks(eng, churn, dep.ticks,
                        np.random.default_rng(dep.seed + 1),
                        ingest_per_tick=dep.tick_records,
                        make_batch=make_batch, warmup=2, live_sids=live,
                        use_channel_plans=True, on_tick=on_tick,
                        on_drain=on_drain, pipeline_depth=2)
        if n > 1:
            assert routed[0] > 0
            mesh_devices = list(eng._mesh.devices.flat)
            assert mesh_devices == devices[:4], mesh_devices
            print(f"routed {routed[0]} sIDs through the shuffle over "
                  f"{[d.id for d in mesh_devices]} == shuffle_notify_ref")
            for i, e in enumerate(eng.shards):
                want = eng.shard_device(i)
                assert want == devices[i]
                state = {"dataset": e.dataset, "index": e.index_state,
                         "group tables": list(e._stacked_cache.values()),
                         "rings": [r for _, _, r in e._rings.values()]}
                for what, tree in state.items():
                    leaves = [x for t in (tree if isinstance(tree, list)
                                          else [tree])
                              for x in device_arrays(t)]
                    assert leaves, (i, what)
                    got = {d for x in leaves for d in x.devices()}
                    assert got == {want}, (i, what, got)
                print(f"shard {i}: dataset, index, group tables and rings on "
                      f"{want}")
        eng.flush_rings()
        drain_to_empty(eng, delivered)
        results[n] = {name: delivered.sid_counts(name)
                      for name in (DRUGS.name, CRIME.name)}
        print(f"{n} shard(s): {rep.ticks} timed churn ticks, {rep.adds} adds,"
              f" {rep.removes} removes, {rep.delivered_sids} sIDs delivered;"
              f" smoke timing (not a metric) {time.perf_counter() - t0:.1f} s"
              f" total", flush=True)
        del eng
    for name in (DRUGS.name, CRIME.name):
        a, b = results[4][name], results[1][name]
        assert same_counts(a, b), name
        assert a.sum() > 0, name
    print("parity: 4 shards delivered the same sID multiset as 1 shard")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded path and its 1-shard "
                         "comparison")
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (devices: {devices}); refusing to run",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    d = devices[0]
    print(f"devices: {devices}")
    print(f"device_kind: {d.device_kind}")
    dep = published()
    if args.four_chips:
        with watch_compiles() as log:
            run_four_chips(dep)
        cache = log.cache
        used = devices[:4]
    else:
        cache = run_one_chip(dep)
        used = devices[:1]
    for dev in used:
        print(f"peak_bytes_in_use {dev}: {peak_bytes(dev)}")
    print(f"compile cache {cache_dir}: hits={cache['cache_hits']} "
          f"misses={cache['cache_misses']}")
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
