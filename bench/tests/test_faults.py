"""The comparison catches a broken timed path: every cell, run end to end at
a tiny size with a fault planted in the program underneath, reads
``correct`` false. The faults a one-chip cell can have: a step that returns
its state unchanged, half of each batch left out, and an answer altered
where it is produced: a join pair's record, a wire line's sID in the
broker's convert stage, or a notified sID in its send stage, the last two
with every count right. (No cell spans chips, so none can leave out an
exchange between them.)"""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from repro.core import broker, plans
from repro.core import records as R
from repro.core.engine import BADEngine
from tiny import tiny_cell

CELLS = [w["name"] for w in run.load_json(run.ROOT / "BENCHMARK.json")
         ["workloads"]]


def state_unchanged(monkeypatch):
    def ingest(self, batch):
        n = batch.num_records
        ids = np.arange(self.size_host, self.size_host + n, dtype=np.int32)
        self.size_host += n
        return ids
    monkeypatch.setattr(BADEngine, "ingest", ingest)


def half_batch(monkeypatch):
    real = BADEngine.ingest

    def ingest(self, batch):
        h = batch.num_records // 2
        return real(self, R.RecordBatch(batch.fields[:h], batch.location[:h]))
    monkeypatch.setattr(BADEngine, "ingest", ingest)


def answer_altered(monkeypatch):
    real = plans.join_param_targets_all

    def join(*args, **kwargs):
        res = real(*args, **kwargs)
        first = jnp.cumsum(res.pair_valid.reshape(res.pair_valid.shape[0],
                                                  -1), axis=1) == 1
        shift = first.reshape(res.pair_valid.shape).astype(jnp.int32)
        return res._replace(pair_rows=res.pair_rows + shift)
    monkeypatch.setattr(plans, "join_param_targets_all", join)


def wire_sid_altered(monkeypatch):
    real = broker._pack_lines

    def pack(*args, **kwargs):
        line, per_broker = real(*args, **kwargs)
        first = broker.HEADER_WORDS
        return line.at[..., first].set(line[..., first] ^ 1), per_broker
    monkeypatch.setattr(broker, "_pack_lines", pack)


def notify_sid_altered(monkeypatch):
    real = broker._member_lookup

    def lookup(group_sids, tgt2, members, cumm, k, ok):
        out = real(group_sids, tgt2, members, cumm, k, ok)
        return jnp.where(ok & (k == 0), out ^ 1, out)
    monkeypatch.setattr(broker, "_member_lookup", lookup)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "answer_altered": answer_altered,
          "wire_sid_altered": wire_sid_altered,
          "notify_sid_altered": notify_sid_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_fault_reads_not_correct(name, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = run.run_cell(tiny_cell(name), 1234, 1.0, False,
                       t_start=time.perf_counter())
    assert out["correct"] is False, out["checks"]
