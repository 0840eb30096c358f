"""END-TO-END DRIVER: serve a model inside the Big Active Data loop.

The paper's EnrichedTweets are produced by an upstream enrichment job (its
ref [32]); here the enrichment IS the engine's post-join stage: raw tweet
records flow through ingestion-time BAD indexing and channel execution,
then a (reduced) qwen2-family LM scores every candidate INSIDE the fused
tick call (``core/enrich.LMScorer`` -> ``launch/serve.prefill_scores``)
and the per-channel delivery budget keeps only the top-scoring pairs —
the full Fig. 1 pipeline with a model in the delivery loop, no host
round-trip between join, scoring, and broker fan-out.

    PYTHONPATH=src python examples/enriched_pipeline.py [--periods 3]

``--heuristic`` swaps the LM for the pure-jnp urgency scorer (fast path,
what the smoke test runs); ``--budget 0`` detaches ranking entirely.
"""
import argparse
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import enrich
from repro.core.channel import most_threatening_tweets, tweets_about_drugs
from repro.core.engine import BADEngine
from repro.core.plans import ExecutionRequest
from repro.data.synthetic import tweet_batch


def build_stage(budget, heuristic=False, prompt_len=16):
    """The enrichment stage: a reduced-LM scorer (one batched prefill per
    tick over the candidate stream) or the heuristic payload scorer."""
    if heuristic:
        return enrich.HeuristicScorer(budget=budget)
    from repro.models.model import ModelApi
    stage = enrich.LMScorer(budget=budget)
    n = ModelApi(stage.cfg).param_count()
    print(f"enrichment model {stage.cfg.name}-reduced ({n:,} params)")
    return stage


def run(periods=3, batch=2048, budget=64, heuristic=False,
        n_subs=2000, capacity=1 << 15):
    """Drive ``periods`` enriched ticks; returns the per-period reports."""
    rng = np.random.default_rng(0)
    eng = BADEngine(dataset_capacity=capacity, index_capacity=capacity // 2,
                    max_window=capacity // 2,
                    max_candidates=max(256, capacity >> 4),
                    brokers=("BrokerA", "BrokerB"))
    eng.create_channel(tweets_about_drugs())
    eng.create_channel(most_threatening_tweets())
    params, brokers = (rng.integers(0, 50, n_subs).astype(np.int32),
                       rng.integers(0, 2, n_subs).astype(np.int32))
    eng.subscribe_bulk("TweetsAboutDrugs", params, brokers)
    eng.subscribe_bulk("MostThreateningTweets", params, brokers)
    if budget:
        eng.set_enrichment(build_stage(budget, heuristic))
    print(f"2 channels, {2 * n_subs} subscriptions, "
          f"budget={budget or 'off'} "
          f"scorer={'heuristic' if heuristic or not budget else 'lm'}")

    out = []
    for period in range(periods):
        # 1. raw feed -> 2. ingestion: conditionsList eval + BAD indexing
        eng.ingest(tweet_batch(rng, batch, t0=1 + period * 600))
        # 3. one fused tick: discovery, join, model scoring + budget rank,
        #    broker fan-out — a single ExecutionRequest, a single jit call
        t0 = time.perf_counter()
        reports = eng.execute(ExecutionRequest(deliver=True, timed=True))
        wall = time.perf_counter() - t0
        for chan, rep in reports.items():
            o = rep.overflow
            print(f"period {period} {chan}: matched={rep.scanned} "
                  f"groups={rep.num_results} notified={rep.num_notified} "
                  f"delivered={o.delivered_pairs} ranked_out={o.ranked_pairs} "
                  f"tick={wall * 1e3:.1f}ms")
        out.append(reports)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--periods", type=int, default=3)
    ap.add_argument("--batch", type=int, default=2048)
    ap.add_argument("--budget", type=int, default=64,
                    help="per-channel delivered-pair budget (0 = no ranking)")
    ap.add_argument("--heuristic", action="store_true",
                    help="use the pure-jnp urgency scorer instead of the LM")
    args = ap.parse_args()
    run(args.periods, args.batch, args.budget, args.heuristic)


if __name__ == "__main__":
    enable_compile_cache()
    main()
