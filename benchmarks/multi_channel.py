"""Multi-channel scaling: vectorized control plane + fused execution.

Measurements the single-channel figures cannot show:

  control plane -- 100k-subscription bulk load through the vectorized
      ``aggregate`` path vs replaying Algorithm 1 one Python call per
      subscription (the paper's broker-side ingest bottleneck).
  data plane    -- one fused ``execute_all`` jitted call driving every
      channel vs the per-channel host loop, at several channel counts;
      since PR 2 the fused call covers spatial channels too (mixed
      param+spatial engine, TweetsAboutCrime in the same plan).
  kernels       -- the fused plan with Pallas ``predicate_filter`` /
      ``spatial_match`` kernels vs the jnp oracle (compiled Pallas is the
      TPU path; in interpret mode off-TPU this records the overhead).
"""
from __future__ import annotations

import time

import numpy as np

from repro.core.channel import (most_threatening_tweets, tweets_about_crime,
                                trending_tweets_in_country, tweets_about_drugs)
from repro.core.engine import BADEngine
from repro.core.plans import ExecutionFlags
from repro.data.synthetic import tweet_batch
from benchmarks.common import emit, scale, timeit

LANGS = ["En", "Pt", "Es", "Ar", "Ja"]


def _replay_load(eng: BADEngine, channel: str, params: np.ndarray,
                 brokers: np.ndarray) -> None:
    """The pre-vectorization path: one Algorithm-1 call per subscription."""
    st = eng.channels[channel]
    for p, b in zip(params.tolist(), brokers.tolist()):
        st.aggregator.add_subscription(p, b)
        st.user_params.add(p)
    st.invalidate_targets()


def _fresh_drug_engine() -> BADEngine:
    eng = BADEngine(dataset_capacity=1 << 16, index_capacity=1 << 14,
                    max_window=1 << 14, max_candidates=1 << 12,
                    brokers=("B1", "B2", "B3", "B4"))
    eng.create_channel(tweets_about_drugs())
    return eng


def bench_bulk_load(rng, repeats: int = 3) -> None:
    n_bulk = scale(100_000, 4096)
    params = rng.integers(0, 50, n_bulk).astype(np.int32)
    brokers = rng.integers(0, 4, n_bulk).astype(np.int32)
    t_replay = t_bulk = float("inf")
    for _ in range(repeats):
        eng = _fresh_drug_engine()
        t0 = time.perf_counter()
        _replay_load(eng, "TweetsAboutDrugs", params, brokers)
        t_replay = min(t_replay, time.perf_counter() - t0)
        g_replay = eng.channels["TweetsAboutDrugs"].aggregator.build()

        eng = _fresh_drug_engine()
        t0 = time.perf_counter()
        eng.subscribe_bulk("TweetsAboutDrugs", params, brokers)
        t_bulk = min(t_bulk, time.perf_counter() - t0)
        g_bulk = eng.channels["TweetsAboutDrugs"].aggregator.build()
    assert g_bulk.num_subscriptions == g_replay.num_subscriptions == n_bulk
    assert g_bulk.num_groups == g_replay.num_groups
    emit("multi_channel/bulk_load/replay", t_replay, f"subs={n_bulk}")
    emit("multi_channel/bulk_load/vectorized", t_bulk,
         f"subs={n_bulk};groups={g_bulk.num_groups}")
    emit("multi_channel/bulk_load/speedup", 0.0,
         f"x{t_replay / t_bulk:.1f} (target >= 10x)")


def _channel_set(n: int, with_spatial: bool = False):
    specs = [tweets_about_drugs(), most_threatening_tweets()]
    if with_spatial:
        specs.append(tweets_about_crime(3))
    specs += [trending_tweets_in_country(i, f"{LANGS[i]}Trending")
              for i in range(len(LANGS))]
    return specs[:n]


def _loaded_engine(rng, specs, n_subs: int, n_tweets: int, n_users: int,
                   use_pallas: bool = False, group_cap=None) -> BADEngine:
    eng = BADEngine(dataset_capacity=1 << 16, index_capacity=1 << 14,
                    max_window=1 << 14, max_candidates=1 << 12,
                    brokers=("B1", "B2", "B3", "B4"), use_pallas=use_pallas,
                    group_cap=group_cap)
    for spec in specs:
        eng.create_channel(spec)
        if spec.join == "param":
            eng.subscribe_bulk(spec.name,
                               rng.integers(0, spec.param_domain, n_subs),
                               rng.integers(0, 4, n_subs))
    if any(s.join == "spatial" for s in specs):
        eng.set_user_locations(
            rng.uniform(-100, 100, size=(n_users, 2)).astype(np.float32),
            rng.integers(0, 4, n_users))
    eng.ingest(tweet_batch(rng, n_tweets, t0=1))
    return eng


def bench_fused_execution(rng, n_channels: int, n_subs: int = None,
                          n_tweets: int = None, with_spatial: bool = False,
                          n_users: int = None, tag: str = "",
                          deliver: bool = False) -> None:
    n_subs = scale(20_000, 1024) if n_subs is None else n_subs
    n_tweets = scale(16_384, 1024) if n_tweets is None else n_tweets
    n_users = scale(2048, 256) if n_users is None else n_users
    specs = _channel_set(n_channels, with_spatial)
    # delivery wire lines carry the sID list per group: bound the group cap
    # to the realistic per-parameter population, not the 40KB frame default
    eng = _loaded_engine(rng, specs, n_subs, n_tweets, n_users,
                         group_cap=64 if deliver else None)
    flags = ExecutionFlags.fully_optimized()

    def sequential():
        return [eng.execute_channel(s.name, flags, advance=False, timed=False,
                                    deliver=deliver)
                for s in specs]

    def fused():
        return eng.execute_all(flags, advance=False, timed=False,
                               deliver=deliver)

    seq_reports = sequential()          # warm every per-channel trace
    fused_reports = fused()             # warm the fused trace
    for s in specs:                     # counts must agree exactly
        r = next(r for r in seq_reports if r.channel == s.name)
        assert fused_reports[s.name].num_results == r.num_results
        assert fused_reports[s.name].num_notified == r.num_notified
        if deliver:                     # ... and so must delivery accounting
            assert fused_reports[s.name].overflow == r.overflow
    t_seq = timeit(sequential)
    t_fused = timeit(fused)
    eng.spill.clear()                   # timing loops re-spill the same tick
    total = sum(r.num_results for r in seq_reports)
    name = f"multi_channel/exec/c{n_channels}{tag}"
    emit(f"{name}/sequential", t_seq, f"results={total}")
    emit(f"{name}/fused", t_fused, f"results={total}")
    emit(f"{name}/speedup", 0.0, f"x{t_seq / t_fused:.2f}")


def bench_fused_pallas_vs_oracle(rng, n_channels: int = 4,
                                 n_subs: int = None,
                                 n_tweets: int = None,
                                 n_users: int = None) -> None:
    """Same mixed param+spatial fused plan, Pallas kernels vs jnp oracle."""
    n_subs = scale(20_000, 1024) if n_subs is None else n_subs
    n_tweets = scale(16_384, 1024) if n_tweets is None else n_tweets
    n_users = scale(2048, 256) if n_users is None else n_users
    specs = _channel_set(n_channels, with_spatial=True)
    seed = rng.integers(0, 2 ** 31)
    times = {}
    results = {}
    for backend, use_pallas in (("oracle", False), ("pallas", True)):
        r = np.random.default_rng(seed)
        eng = _loaded_engine(r, specs, n_subs, n_tweets, n_users,
                             use_pallas=use_pallas)
        flags = ExecutionFlags.fully_optimized()
        reports = eng.execute_all(flags, advance=False, timed=False)  # warm
        results[backend] = {n: rep.num_results for n, rep in reports.items()}
        times[backend] = timeit(
            lambda: eng.execute_all(flags, advance=False, timed=False))
    # Predicate evaluation is integer-exact between kernel and oracle, and
    # the spatial kernel evaluates the oracle's own (t-u)^2 formula.
    assert results["pallas"] == results["oracle"], results
    total = sum(results["oracle"].values())
    emit(f"multi_channel/exec/mixed{n_channels}/fused_oracle",
         times["oracle"], f"results={total}")
    emit(f"multi_channel/exec/mixed{n_channels}/fused_pallas",
         times["pallas"], f"results={total}")
    emit(f"multi_channel/exec/mixed{n_channels}/pallas_vs_oracle", 0.0,
         f"x{times['oracle'] / times['pallas']:.2f} "
         "(>1 means pallas faster; expect <1 in interpret mode off-TPU)")


def run(rng) -> None:
    bench_bulk_load(rng)
    for n in (2, 4, 7):
        bench_fused_execution(rng, n)
    # mixed param+spatial engine: the spatial channel rides the same fused
    # call (acceptance: >= 4 channels, fused-vs-sequential + speedup)
    for n in (4, 8):
        bench_fused_execution(rng, n, with_spatial=True, tag="mixed")
    # end-to-end WITH broker delivery: the convert+send stages ride the same
    # jitted call in the fused path vs one jitted delivery per channel in the
    # sequential loop (acceptance: fused delivery wins at >= 4 channels)
    for n in (4, 7):
        bench_fused_execution(rng, n, tag="deliver", deliver=True)
    bench_fused_pallas_vs_oracle(rng)


if __name__ == "__main__":
    run(np.random.default_rng(0))
