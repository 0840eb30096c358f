"""Seeded data for one cell: the record stream, subscribers and located users.

The field distributions are a copy of the program's EnrichedTweets generator
(``repro.data.synthetic.tweet_batch``, ``subscriptions_by_population``,
``STATE_WEIGHTS``, ``LANG_WEIGHTS``), kept here so that a change to the
program cannot change the traffic. Everything is vectorised numpy and is drawn from
``--seed`` alone: one independent stream per component, so the preload, the
ticks, the subscribers and the users do not shift when another component's
size changes.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

# Rough relative US state populations (50 entries, normalised at use).
STATE_WEIGHTS = np.array([
    39, 30, 22, 21, 13, 12.8, 11.8, 10.8, 10.7, 10.0,
    9.3, 8.9, 7.9, 7.3, 7.2, 6.9, 6.3, 6.2, 6.1, 5.9,
    5.8, 5.1, 4.9, 4.6, 4.5, 4.4, 3.4, 3.2, 3.2, 3.1,
    3.0, 2.9, 2.3, 2.2, 2.1, 2.0, 1.9, 1.9, 1.8, 1.5,
    1.4, 1.3, 1.1, 1.1, 1.0, 0.97, 0.91, 0.78, 0.65, 0.58,
])

LANG_WEIGHTS = np.array([0.62, 0.18, 0.08, 0.06, 0.06])  # en, pt, es, ar, ja

# independent numpy streams per component of a cell's data
PRELOAD, TICKS, SUBSCRIBERS, USERS, SAMPLE = range(5)
# the stream whose executions' size classes every seed reproduces
SIZES_SEED = 0
# draws allowed to find a batch of a given size class (the rarer class of
# a cell comes up in about one draw in three)
MAX_DRAWS = 200


def rng_for(seed: int, component: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), component])


def tweets(rng: np.random.Generator, n: int, t0: int, rate_per_s: int,
           schema: List[str], first: int = 0) -> tuple:
    """``n`` EnrichedTweets with the paper's selectivities: predicates I–III
    50% each, IV–V 20% each, states by population, languages skewed;
    record ``first + i`` of a stream at ``rate_per_s`` from second ``t0``.
    Returns ``(fields (n, F) int32, locations (n, 2) float32)``."""
    col = {name: i for i, name in enumerate(schema)}
    f = np.zeros((n, len(schema)), dtype=np.int32)
    f[:, col["state"]] = rng.choice(50, size=n,
                                    p=STATE_WEIGHTS / STATE_WEIGHTS.sum())
    f[:, col["about_country"]] = (rng.random(n) > 0.5).astype(np.int32)
    f[:, col["retweet_count"]] = np.where(rng.random(n) < 0.5,
                                          rng.integers(10001, 200000, n),
                                          rng.integers(0, 10001, n))
    f[:, col["hate_speech_rate"]] = np.where(rng.random(n) < 0.5,
                                             rng.integers(6, 11, n),
                                             rng.integers(0, 6, n))
    f[:, col["threatening_rate"]] = np.where(rng.random(n) < 0.2,
                                             rng.integers(6, 11, n),
                                             rng.integers(0, 6, n))
    f[:, col["weapon_mentioned"]] = (rng.random(n) < 0.2).astype(np.int32)
    f[:, col["drug_activity"]] = rng.integers(0, 5, n)
    f[:, col["lang"]] = rng.choice(5, size=n, p=LANG_WEIGHTS)
    f[:, col["country"]] = rng.integers(0, 200, n)
    f[:, col["timestamp"]] = t0 + (first + np.arange(n)) // max(1, rate_per_s)
    loc = rng.uniform(-100, 100, size=(n, 2)).astype(np.float32)
    return f, loc


def subscriber_params(rng: np.random.Generator, n: int, kind: str,
                      domain: int) -> np.ndarray:
    """Each subscriber's parameter: its state, by population (§5.2)."""
    if kind != "state_population" or domain != STATE_WEIGHTS.size:
        raise ValueError(f"subscriber_params {kind!r} over a domain of "
                         f"{domain}: only state_population over 50 states")
    p = rng.choice(domain, size=n, p=STATE_WEIGHTS / STATE_WEIGHTS.sum())
    return p.astype(np.int32)


@dataclasses.dataclass
class CellData:
    """Everything a run ingests or subscribes, made from the seed before the
    window. ``batches[k]`` is execution k's (fields, locations); the closed
    loop cycles through them, the open loop takes each once."""

    preload_fields: np.ndarray
    preload_locs: np.ndarray
    batches: List[tuple]
    sub_params: np.ndarray       # (subscribers,) int32
    sub_brokers: np.ndarray      # (subscribers,) int32
    user_locs: np.ndarray        # (users, 2) float32
    user_brokers: np.ndarray     # (users,) int32


def size_class(cfg: Dict, fields: np.ndarray) -> int:
    """An execution's size class: the power of two at or above the largest
    number of its records that one channel's fixed conjunction matches. The
    engine sizes its per-execution work by it (candidate buckets)."""
    from bench.reference import where_mask
    n = max(int(where_mask(fields, ch["where"], cfg["schema"]).sum())
            for ch in cfg["channels"])
    return 1 << max(0, (max(n, 1) - 1).bit_length())


def executions(cfg: Dict, batch_records: int, n_batches: int, seed: int
               ) -> List[tuple]:
    """The records of ``n_batches`` executions. Every seed gets the same
    sequence of size classes: the classes are those of a fixed seed-0
    stream, and the seed's own stream is drawn again, batch by batch, until
    each batch has its class. So a seed changes which records arrive, not
    how much work each execution is."""
    schema, rate = cfg["schema"], cfg["records_per_s"]
    t0 = cfg["preload_records"] // rate
    fixed, rng = rng_for(SIZES_SEED, TICKS), rng_for(seed, TICKS)
    out = []
    for b in range(n_batches):
        first = b * batch_records
        want = size_class(cfg, tweets(fixed, batch_records, t0, rate, schema,
                                      first)[0])
        for _ in range(MAX_DRAWS):
            f, l = tweets(rng, batch_records, t0, rate, schema, first)
            if size_class(cfg, f) == want:
                break
        else:
            raise RuntimeError(f"no batch of size class {want} in "
                               f"{MAX_DRAWS} draws")
        out.append((f, l))
    return out


def make(cfg: Dict, batch_records: int, n_batches: int, seed: int
         ) -> CellData:
    pf, pl = tweets(rng_for(seed, PRELOAD), cfg["preload_records"], 0,
                    cfg["records_per_s"], cfg["schema"])
    batches = executions(cfg, batch_records, n_batches, seed)
    domain = max((c["param_domain"] for c in cfg["channels"]
                  if c["join"] == "param"), default=1)
    rs = rng_for(seed, SUBSCRIBERS)
    params = subscriber_params(rs, cfg["subscribers"],
                               cfg["subscriber_params"], domain)
    brokers = rs.integers(0, cfg["brokers"], cfg["subscribers"]).astype(
        np.int32)
    ru = rng_for(seed, USERS)
    users = cfg.get("located_users", 0)
    user_locs = ru.uniform(-100, 100, (users, 2)).astype(np.float32)
    user_brokers = ru.integers(0, cfg["brokers"], users).astype(np.int32)
    return CellData(pf, pl, batches, params, brokers, user_locs, user_brokers)
