"""CPU rehearsal of ``chip_smoke.py``: its phases at a tiny size.

The same phase functions the script runs on the chip, with every shape kept
and every count cut (``chip_smoke.tiny()``), Pallas kernels in interpret
mode. Run these before sending the script to a TPU.
"""
import collections
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

SCRIPT = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod       # dataclasses resolve their module
    spec.loader.exec_module(mod)
    yield mod
    del sys.modules[spec.name]


@pytest.fixture(scope="module")
def tiny(cs):
    dep = cs.tiny()
    data = cs.make_data(dep)
    return dep, data, cs.reference(dep, data)


def test_reference_matches_brute_force(cs, tiny):
    """The numpy reference's aggregate counts equal a per-(record,
    subscriber) evaluation of the channel semantics."""
    dep, data, ref = tiny
    for t in range(dep.ticks):
        f = data.fields[cs.tick_rows(dep, t)]
        hit = cs._matches(f, cs.DRUGS)
        pairs = (f[hit, cs.DRUGS.param_field][:, None]
                 == data.sub_params[None, :])
        assert ref.notified[cs.DRUGS.name][t] == int(pairs.sum())
    assert ref.notified[cs.DRUGS.name] != [0] * dep.ticks
    assert ref.crime_keys.size == sum(ref.notified[cs.CRIME.name]) > 0
    assert int(ref.sid_counts[cs.CRIME.name].sum()) == ref.crime_keys.size


@pytest.mark.parametrize("backend", ["oracle", "pallas", "compact_pallas"])
def test_backend_matches_reference(cs, tiny, backend):
    """Every tick agrees with the numpy reference and conserves, delivered
    content is the reference's, and off a TPU no kernel compiles to Mosaic
    (the check the chip run inverts)."""
    dep, data, ref = tiny
    res = cs.run_backend(dep, data, ref, backend)
    for name, want in ref.sid_counts.items():
        assert cs.same_counts(res["counts"][name], want)
    assert res["pairs"] == sum(ref.notified[cs.DRUGS.name]) + sum(
        ref.notified[cs.CRIME.name])
    mosaic = res["compiles"].mosaic
    assert {"jit_ingest_step", "jit_run"} <= set(mosaic)
    assert not any(mosaic.values())


def test_conservation_check_rejects_loss(cs):
    stats = collections.namedtuple("Stats", [
        "delivered_pairs", "spilled_pairs", "dropped_pairs", "retried_pairs",
        "delivered_sids", "spilled_sids", "dropped_sids", "retried_sids"])
    cs.check_conservation(stats(5, 2, 1, 0, 9, 0, 0, 1), 8, 8)
    with pytest.raises(AssertionError):
        cs.check_conservation(stats(5, 2, 0, 0, 9, 0, 0, 1), 8, 8)


def test_one_chip_phase(cs, capsys):
    """The whole one-chip phase: all three backends, then their parity."""
    cs.run_one_chip(cs.tiny())
    out = capsys.readouterr().out
    assert "parity: oracle, pallas, compact_pallas" in out


@pytest.mark.multidevice
def test_four_chip_phase(cs, multidevice, capsys):
    """The ``--four-chips`` phase on four host devices: sharded vs 1-shard
    parity, routed buffers vs ``shuffle_notify_ref``, per-shard placement."""
    cs.run_four_chips(cs.tiny())
    out = capsys.readouterr().out
    assert "parity: 4 shards delivered the same sID multiset" in out
    for i in range(4):
        assert f"shard {i}: dataset, index, group tables and rings on" in out


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_entry_refuses_without_tpu(tmp_path, where):
    """On the CPU, and in a directory holding only the script, the entry
    exits non-zero and prints no result line."""
    script = SCRIPT
    if where == "alone":
        script = tmp_path / SCRIPT.name
        shutil.copy(SCRIPT, script)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
