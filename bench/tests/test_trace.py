"""The reduction from trace to metrics, on a small trace recorded on a v5e
(``record_trace.py``: the first 400 ms of a traced ``bad51.drain`` window),
and on hand-made traces whose answers are known."""
import pathlib

import pytest

from bench import kernels, peaks
from bench import trace as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _mini():
    """Two programs on one chip inside a 10 us window: an ingest (with one
    custom call) and a plan-group call, and host spans over the gaps."""
    ops = [["fusion.1", 1000, 1000, False],
           ["custom-call.2", 2000, 500, True],
           ["fusion.3", 6000, 2000, False],
           ["fusion.4", 7000, 1500, False]]      # overlaps fusion.3
    mods = [["jit_ingest_step(7)", 1000, 1500, False],
            ["jit_run(9)", 6000, 2500, False]]
    host = [["window", 0, 10000], ["ingest", 0, 2600], ["step", 2600, 6000],
            ["flush", 8600, 1400]]
    return {"device": {"/device:TPU:0": {"XLA Ops": ops,
                                         "XLA Modules": mods}},
            "host": host}


def test_reduce_hand_made():
    r = tr.reduce(_mini())
    assert r.window_s == pytest.approx(10e-6)
    # busy = [1000, 2500) + [6000, 8500) = 4000 ns
    assert r.busy_s == pytest.approx(4e-6)
    assert r.modules["jit_ingest_step"] == [1, pytest.approx(1.5e-6)]
    assert r.modules["jit_run"] == [1, pytest.approx(2.5e-6)]
    assert r.custom == {"jit_ingest_step": [1, pytest.approx(0.5e-6)]}
    b = r.breakdown()
    assert b["device_ops"][0] == ["jit_run:fusion.3", pytest.approx(2e-6)]
    # the longest idle gap, [2500, 6000), is inside the host's step span
    assert b["idle_gaps"][0] == ["step", pytest.approx(3.5e-6)]


def test_events_outside_the_window_do_not_count():
    t = _mini()
    t["device"]["/device:TPU:0"]["XLA Ops"].append(["late", 20000, 500,
                                                     False])
    assert tr.reduce(t).busy_s == pytest.approx(4e-6)


def test_recorded_v5e_trace():
    path = DATA / "bad51_drain_v5e.json.gz"
    r = tr.reduce(tr.load_json(str(path)))
    assert 0 < r.busy_s <= r.window_s
    assert r.modules["jit_ingest_step"][0] > 0
    assert r.modules["jit_run"][0] > 0
    # predicate_filter is the ingest program's one custom call
    n, secs = r.custom["jit_ingest_step"]
    assert n == r.modules["jit_ingest_step"][0]
    least, bound = kernels.least_time_s(
        kernels.predicate_filter_cost(16384, 10, 2), "TPU v5 lite")
    assert bound == "bytes"
    assert 0 < least / (secs / n) <= 1.0
    assert len(r.breakdown()["device_ops"]) == 10


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9000")
