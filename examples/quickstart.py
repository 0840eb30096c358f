"""Quickstart: create a channel, subscribe, ingest tweets, execute, deliver.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import records as R
from repro.core.channel import tweets_about_drugs
from repro.core.engine import BADEngine
from repro.core.plans import ExecutionFlags
from repro.data.synthetic import drug_tweak, tweet_batch


def main():
    rng = np.random.default_rng(0)
    eng = BADEngine(dataset_capacity=1 << 14, index_capacity=1 << 13,
                    max_window=1 << 13, max_candidates=1 << 10,
                    brokers=("BrokerA", "BrokerB"))

    # Developer: CREATE CONTINUOUS PUSH CHANNEL TweetsAboutDrugs(MyState)
    eng.create_channel(tweets_about_drugs())

    # Subscribers: SUBSCRIBE TO TweetsAboutDrugs("CA") ON BrokerA; ...
    for state, broker in [(4, "BrokerA"), (4, "BrokerA"), (4, "BrokerB"),
                          (27, "BrokerA")]:
        sid = eng.subscribe("TweetsAboutDrugs", state, broker)
        print(f"subscribed sid={sid} state={state} via {broker}")

    # Data feed: one period of tweets (fixed predicates are evaluated at
    # ingestion; matching PKs land in the channel's BAD index).
    batch = tweet_batch(rng, 4096, t0=1)
    fields = drug_tweak(np.asarray(batch.fields).copy(), rng, 0.05)
    eng.ingest(R.RecordBatch.from_numpy(fields, np.asarray(batch.location)))

    # Channel execution under the fully optimized plan.
    rep = eng.execute_channel("TweetsAboutDrugs",
                              ExecutionFlags.fully_optimized())
    print(f"\nresults (group records): {rep.num_results}")
    print(f"subscribers notified:    {rep.num_notified}")
    print(f"records scanned:         {rep.scanned} (BAD index window)")
    print(f"bytes to brokers:        {rep.broker_bytes.tolist()}")

    # Compare against the original (pre-optimization) plan.
    eng2 = BADEngine(dataset_capacity=1 << 14, index_capacity=1 << 13,
                     max_window=1 << 13, max_candidates=1 << 10,
                     brokers=("BrokerA", "BrokerB"))
    eng2.create_channel(tweets_about_drugs())
    for state, broker in [(4, "BrokerA"), (4, "BrokerA"), (4, "BrokerB"),
                          (27, "BrokerA")]:
        eng2.subscribe("TweetsAboutDrugs", state, broker)
    eng2.ingest(R.RecordBatch.from_numpy(fields, np.asarray(batch.location)))
    rep0 = eng2.execute_channel("TweetsAboutDrugs", ExecutionFlags.original())
    print(f"\noriginal plan: scanned={rep0.scanned} results={rep0.num_results} "
          f"(same {rep0.num_notified} notified)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
