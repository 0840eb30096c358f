"""The engine's own spans, on the profiler's clock.

Inside the fused plan-group call the stages run under named scopes
(``bad.discover``, ``bad.join``, ``bad.convert``, ``bad.send``,
``bad.ring``), which tag the ops' metadata in the device trace. On the host,
``jax.profiler.TraceAnnotation`` spans cover ingest, dispatch and sync, and
the sync's report span carries the send stage's counters as arguments. These
tests check, on the CPU with a tiny engine:

  * the lowered fused call carries all five scopes, in the padded and the
    compact path, with and without a retry ring;
  * a profiler trace of two ``TickPipeline`` steps holds the host spans,
    nested as named;
  * the ``notify_slots`` / ``produced_sids`` arguments equal the engine's
    ``max_notify * C`` and the produced count of its ``DeliveryStats``.
"""
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.channel import tweets_about_crime, tweets_about_drugs
from repro.core.engine import BADEngine
from repro.core.plans import ExecutionFlags, ExecutionRequest
from repro.core.runtime import TickPipeline

from conftest import make_tweets

FLAGS = ExecutionFlags.fully_optimized()          # bad_index, aggregated
STAGES = ("bad.discover", "bad.join", "bad.convert", "bad.send", "bad.ring")
MAX_NOTIFY = 24

# every host span and the span it must sit in (None: outermost)
PARENT = {
    "bad.ingest": None,
    "bad.step": None,
    "bad.flush": None,
    "bad.dispatch": "bad.step",
    "bad.dispatch.group": "bad.dispatch",
    "bad.dispatch.bucket_read": "bad.dispatch.group",
    "bad.dispatch.args": "bad.dispatch.group",
    "bad.dispatch.launch": "bad.dispatch.group",
    "bad.sync": ("bad.step", "bad.flush"),
    "bad.sync.group": "bad.sync",
    "bad.sync.wait": "bad.sync.group",
    "bad.sync.copy": "bad.sync.group",
    "bad.sync.spill": "bad.sync.group",
    "bad.sync.report": "bad.sync.group",
}


def _engine(rng, ring_capacity=24):
    """A param and a spatial channel, capped so that both overflow."""
    eng = BADEngine(dataset_capacity=4096, index_capacity=1024,
                    max_window=2048, max_candidates=512,
                    brokers=("B1", "B2"), group_cap=8,
                    max_deliver_pairs=12, max_notify=MAX_NOTIFY,
                    ring_capacity=ring_capacity)
    eng.create_channel(tweets_about_drugs())
    eng.create_channel(tweets_about_crime(1))
    eng.set_user_locations((rng.normal(size=(30, 2)) * 30).astype(np.float32),
                           rng.integers(0, 2, 30))
    eng.subscribe_bulk("TweetsAboutDrugs", rng.integers(0, 50, 200),
                       rng.integers(0, 2, 200))
    return eng


@pytest.mark.parametrize("ring_capacity", [0, 24], ids=["plain", "ring"])
@pytest.mark.parametrize("backend", ["oracle", "compact"])
def test_fused_call_carries_stage_scopes(backend, ring_capacity,
                                         monkeypatch):
    rng = np.random.default_rng(3)
    eng = _engine(rng, ring_capacity)
    eng.ingest(make_tweets(rng, 64, t0=100, match_drugs=0.3))
    lowered = []
    real = eng._exec_all_fn

    def spy(*a, **k):
        fn, key = real(*a, **k)

        def call(*args):
            shapes = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
            lowered.append(fn.lower(*shapes).as_text(debug_info=True))
            return fn(*args)
        return call, key

    monkeypatch.setattr(eng, "_exec_all_fn", spy)
    eng.dispatch(ExecutionRequest(flags=FLAGS, backend=backend,
                                  deliver=True)).sync()
    assert lowered
    for text in lowered:
        for scope in STAGES:
            assert f"/{scope}/" in text, scope


def _trace_two_steps(tmp_path):
    rng = np.random.default_rng(5)
    eng = _engine(rng)
    pipe = TickPipeline(eng, depth=2)
    batches = [make_tweets(rng, 64, t0=100 * (t + 1), match_drugs=0.3)
               for t in range(2)]
    got = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        for b in batches:
            eng.ingest(b)
            got += pipe.step(FLAGS, deliver=True)
        got += pipe.flush()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("bad."):
                    spans.append((line.name, e.name, e.start_ns,
                                  e.start_ns + e.duration_ns, dict(e.stats)))
    return spans, got


def _innermost_parent(span, spans):
    line, _, s, e, _ = span
    around = [o for o in spans if o is not span and o[0] == line
              and o[2] <= s and e <= o[3]]
    return max(around, key=lambda o: o[2])[1] if around else None


def test_host_spans_nest_as_named(tmp_path):
    spans, got = _trace_two_steps(tmp_path)
    assert [t for t, _ in got] == [0, 1]
    names = [s[1] for s in spans]
    assert set(names) == set(PARENT)
    for span in spans:
        want = PARENT[span[1]]
        want = want if isinstance(want, tuple) else (want,)
        assert _innermost_parent(span, spans) in want, span[1]
    assert names.count("bad.step") == 2 and names.count("bad.flush") == 1
    assert names.count("bad.ingest") == 2
    # one plan-group per dispatch; per sync one report per join group
    assert names.count("bad.dispatch.group") == 2
    assert names.count("bad.sync.group") == 2
    assert names.count("bad.sync.report") == 4


def test_send_counters_match_engine(tmp_path):
    spans, got = _trace_two_steps(tmp_path)
    reports = sorted((s for s in spans if s[1] == "bad.sync.report"),
                     key=lambda s: s[2])
    # in time order: tick 0's param then spatial group, then tick 1's
    want = [(tick, name) for tick, _ in got
            for name in ("TweetsAboutDrugs", "TweetsAboutCrime1")]
    by_tick = dict(got)
    produced_total = 0
    for (tick, name), span in zip(want, reports, strict=True):
        args = span[4]
        o = by_tick[tick][name].overflow
        produced = o.delivered_sids + o.spilled_sids + o.dropped_sids
        assert args["notify_slots"] == MAX_NOTIFY          # C == 1
        assert args["produced_sids"] == produced
        produced_total += produced
    # the caps make the send stage overflow: produced exceeds what fits
    assert produced_total > len(reports) * MAX_NOTIFY
