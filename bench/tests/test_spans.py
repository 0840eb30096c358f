"""The reduction of the engine's own spans (``bench/spans.py``): on a
hand-made trace whose answers are known, on the recorded v5e traces, and
against ``bench/trace.py``, whose numbers it must leave as they are."""
import importlib.util
import pathlib

import pytest

from bench import run, spans
from bench import trace as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"
OLD = DATA / "bad51_drain_v5e.json.gz"
SCOPED = DATA / "bad51_drain_v5e_scoped.json.gz"


def _mini():
    """One plan-group call inside a 20 us window, its ops in three stages
    (a ``while`` in ``bad.send`` holds a fusion of its own), and the host
    spans of one step: dispatch, then a sync whose wait covers the call."""
    ops = [["fusion.1", 1000, 1000, False],          # bad.discover
           ["fusion.2", 2500, 500, False],           # unscoped
           ["while.3", 4000, 3000, False],           # bad.send
           ["fusion.4", 4500, 2000, False],          # bad.send, in while.3
           ["fusion.5", 8000, 1000, False],          # bad.ring
           ["fusion.6", 12000, 1000, False]]         # bad.discover
    mods = [["jit_run(9)", 1000, 8000, False],
            ["jit_run(9)", 12000, 1000, False]]
    scopes = {"jit_run(9):fusion.1": "bad.discover",
              "jit_run(9):while.3": "bad.send",
              "jit_run(9):fusion.4": "bad.send",
              "jit_run(9):fusion.5": "bad.ring",
              "jit_run(9):fusion.6": "bad.discover"}
    host = [["window", 0, 20000],
            ["step", 0, 11000],
            ["bad.step", 100, 10800, {}],
            ["bad.dispatch", 200, 800, {}],
            ["bad.dispatch.group", 300, 600, {}],
            ["bad.dispatch.bucket_read", 300, 100, {}],
            ["bad.dispatch.args", 400, 300, {}],
            ["bad.dispatch.launch", 700, 200, {}],
            ["bad.sync", 1100, 9700, {}],
            ["bad.sync.group", 1100, 9600, {}],
            ["bad.sync.wait", 1100, 8000, {}],
            ["bad.sync.copy", 9100, 500, {}],
            ["bad.sync.spill", 9600, 100, {}],
            ["bad.sync.report", 9700, 1000,
             {"notify_slots": 64, "produced_sids": 16}],
            ["bad.dispatch.args", 11000, 1000, {}],   # no benchmark span
            ["bad.sync.report", 25000, 100,           # after the window
             {"notify_slots": 64, "produced_sids": 64}]]
    return {"device": {"/device:TPU:0": {"XLA Ops": ops,
                                         "XLA Modules": mods}},
            "host": host, "scopes": scopes}


def test_scopes_spans_counters_and_gaps():
    sp = spans.reduce(_mini())
    # stage time is the union of the stage's op intervals (op times nest)
    assert sp.scope_s == {"bad.discover": pytest.approx(2e-6),
                          "bad.send": pytest.approx(3e-6),
                          "bad.ring": pytest.approx(1e-6)}
    count, total, own = sp.span_s["bad.dispatch.group"]
    assert count == 1
    assert total == pytest.approx(600e-9)
    assert own == pytest.approx(0.0)           # its children cover it
    assert sp.span_s["bad.sync"][2] == pytest.approx(100e-9)
    assert sp.span_s["bad.sync.group"][2] == pytest.approx(0.0)
    assert sp.span_s["bad.step"][2] == pytest.approx(
        (10800 - 800 - 9700) * 1e-9)
    assert sp.span_s["bad.dispatch.args"][0] == 2
    # a span that starts after the window adds no counter
    assert sp.counters == {"notify_slots": 64, "produced_sids": 16}
    keys = dict(sp.breakdown()["device_ops"])
    assert keys["jit_run/bad.send:while.3"] == pytest.approx(3e-6)
    assert keys["jit_run:fusion.2"] == pytest.approx(0.5e-6)
    # gaps, longest first: [13000, 20000) with no span open; [9000, 12000)
    # in the benchmark's step and the program's report span at its middle;
    # then the 1 us gaps in the dispatch's args and the sync's wait
    assert sp.gaps[0] == ("idle", pytest.approx(7e-6))
    assert sp.gaps[1] == ("step/bad.sync.report", pytest.approx(3e-6))
    assert {n for n, s in sp.gaps[2:5]} == {"step/bad.dispatch.args",
                                            "step/bad.sync.wait"}
    assert [s for n, s in sp.gaps[2:]] == pytest.approx([1e-6] * 3
                                                        + [0.5e-6])


def test_metrics_per_execution():
    sp = spans.reduce(_mini())
    m = spans.metrics(sp, executions=2)
    assert m["discover.device_ms"] == pytest.approx(1e-3)
    assert m["send.device_ms"] == pytest.approx(1.5e-3)
    assert "join.device_ms" not in m
    assert m["send_slot_use_pct"] == pytest.approx(25.0)
    # waits: the bucket read and the sync's block on the call
    assert m["device_wait_ms"] == pytest.approx((100 + 8000) * 1e-6 / 2)
    # the rest: every other span's self time, both args spans included
    host_ns = 10800 - 8100
    assert m["host_work_ms"] == pytest.approx((host_ns + 1000) * 1e-6 / 2)


def test_a_trace_without_engine_spans_reads_nothing_new():
    t = _mini()
    t.pop("scopes")
    t["host"] = [h for h in t["host"] if not h[0].startswith("bad.")]
    sp = spans.reduce(t)
    assert sp.scope_s == {} and sp.span_s == {} and sp.counters == {}
    assert spans.metrics(sp, 2) == {}
    assert sp.gaps[1] == ("step", pytest.approx(3e-6))


def _readers():
    return sorted(p.stem for p in (run.BENCH / "metrics").glob("*.py"))


@pytest.mark.parametrize("path", [OLD, SCOPED], ids=["old", "scoped"])
def test_trace_reduce_reads_as_before(path):
    """``spans.load``'s extra keys and host fields leave every number
    ``trace.reduce`` gives, and so every existing reader, as it was."""
    t = tr.load_json(str(path))
    plain = {"device": t["device"],
             "host": [h[:3] for h in t["host"]
                      if not h[0].startswith("bad.")]}
    a, b = tr.reduce(plain), spans.reduce(t).base
    assert _same(a, b)


def _same(a, b) -> bool:
    return (a.window_s, a.busy_s, a.modules, a.custom, a.ops, a.gaps) == (
        b.window_s, b.busy_s, b.modules, b.custom, b.ops, b.gaps)


def test_existing_readers_read_the_old_trace_as_before():
    from bench.loops import Execution
    red = tr.reduce(tr.load_json(str(OLD)))
    base = spans.reduce(tr.load_json(str(OLD))).base
    cfg = run.load_json(run.ROOT / "bench/configs/bad51_enriched_tweets.json")
    execs = [Execution(k, 0, 0, 16384, None, done=1.0) for k in range(2)]
    for name in _readers():
        spec = importlib.util.spec_from_file_location(
            f"r_{name.replace('.', '_')}", run.BENCH / "metrics" / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        got = [mod.read(run.Run("closed", 1.0, (0.0, 1.0), execs, [], r,
                                "TPU v5 lite", cfg)) for r in (red, base)]
        assert got[0] == got[1], name


def test_recorded_scoped_trace_reads_every_stage():
    t = tr.load_json(str(SCOPED))
    sp = spans.reduce(t)
    execs = sp.base.modules["jit_run"][0]
    m = spans.metrics(sp, execs)
    for stage in spans.STAGES:
        assert m[f"{stage[len('bad.'):]}.device_ms"] > 0, stage
    # the stages cover the plan-group call
    run_s = sp.base.modules["jit_run"][1]
    assert sum(sp.scope_s.values()) <= run_s * 1.001
    assert 0 < m["send_slot_use_pct"] < 100
    assert any("/" in n for n, _ in sp.gaps)


def test_load_reads_scopes_from_the_programs_hlo(tmp_path):
    """The stages come from the HLO the profiler keeps in its metadata
    plane (the ops' events on a TPU carry no ``op_name``), and the engine's
    host spans keep their arguments."""
    import glob

    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("bad.discover"):
            y = jnp.sin(x) * 2
        with jax.named_scope("bad.send"):
            return jnp.searchsorted(jnp.cumsum(y), y)

    fn = jax.jit(f)
    x = jnp.arange(256.0)
    fn(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bad.sync.report", notify_slots=8,
                                          produced_sids=3):
            fn(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    t = spans.load(path)
    by_module = {}
    for key, scope in t["scopes"].items():
        module, _ = key.rsplit(":", 1)
        by_module.setdefault(module.split("(")[0], set()).add(scope)
    assert by_module["jit_f"] == {"bad.discover", "bad.send"}
    report, = [h for h in t["host"] if h[0] == "bad.sync.report"]
    assert report[3] == {"notify_slots": 8, "produced_sids": 3}
