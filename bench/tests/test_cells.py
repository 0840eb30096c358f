"""Every cell of BENCHMARK.json, end to end at a tiny size on the CPU (Pallas
kernels in interpret mode): the run is correct, reports each of its metrics
under the keys a result line carries, and compiles nothing inside its window."""
import contextlib
import json
import time

import pytest

from bench import compiles, run
from tiny import tiny_cell

CELLS = [w["name"] for w in run.load_json(run.ROOT / "BENCHMARK.json")
         ["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_correct(name):
    c = tiny_cell(name)
    out = run.run_cell(c, 2**31 + 12345, 1.5, False,
                       t_start=time.perf_counter())
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == set(c.end_to_end), out["metrics"]
    for name_, m in out["metrics"].items():
        assert m["unit"] == c.end_to_end[name_]["unit"]
        assert m["value"] > 0
    assert list(out)[-1] == "checks"
    assert out["device"]["count"] == 1
    json.dumps(out)


@pytest.mark.parametrize("name", CELLS)
def test_window_compiles_nothing(name, monkeypatch):
    """The warm-up covers every shape the window uses."""
    logs = []
    real = compiles.watch_compiles

    @contextlib.contextmanager
    def spy():
        with real() as log:
            logs.append(log)
            yield log

    monkeypatch.setattr(compiles, "watch_compiles", spy)
    out = run.run_cell(tiny_cell(name), 99, 1.5, False,
                       t_start=time.perf_counter())
    assert out["correct"]
    setup, window = logs
    assert setup.programs > 0
    assert window.programs == 0, window


def test_traced_run_reports_per_layer_metrics_it_can_read():
    """With --trace 1 the line carries busy and window seconds and a
    breakdown; on the CPU there is no device plane, so the readers of device
    programs find nothing and leave their metric out."""
    c = tiny_cell("bad51.alerts")
    out = run.run_cell(c, 5, 1.5, True, t_start=time.perf_counter())
    assert out["correct"]
    assert set(out["metrics"]) <= set(c.per_layer)
    assert "execution_lag_ms.alerts" in out["metrics"]
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_same_seed_same_inputs():
    from bench import generator
    c = tiny_cell("bad51.drain")
    a = generator.make(c.cfg, 1024, 2, 2**31 + 7)
    b = generator.make(c.cfg, 1024, 2, 2**31 + 7)
    d = generator.make(c.cfg, 1024, 2, 2**31 + 8)
    assert (a.batches[1][0] == b.batches[1][0]).all()
    assert (a.sub_params == b.sub_params).all()
    assert not (a.batches[1][0] == d.batches[1][0]).all()
