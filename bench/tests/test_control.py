"""The control of the comparison: the reference one precision step down,
put in the engine's place, must read not correct in every cell through the
harness's own comparison; the reference's own table layout must pass the
table check it is held to."""
import numpy as np
import pytest

from bench import check, control, generator, reference, run
from tiny import tiny_cell

CELLS = [w["name"] for w in run.load_json(run.ROOT / "BENCHMARK.json")
         ["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_control_reads_not_correct(name):
    out = control.control(tiny_cell(name), 2**31 + 99, 2.0)
    both = out["fields+locations"]
    assert both["correct"] is False, out
    assert both["wire_off"] > 0 and both["notify_off"] > 0, out
    assert out["sampled"] > 0


def test_locations_step_moves_the_spatial_join():
    """bfloat16 distances move the spatial channel's answers."""
    a = control.control(tiny_cell("bad51.alerts"), 11, 4.0)["locations"]
    assert a["correct"] is False
    assert a["counts_off"] + a["pairs_off"] > 0


def test_stand_in_at_the_stated_precision_reads_correct():
    """With no precision step the stand-in is the reference itself: every
    compared number, the wire buffers' included, reads 0."""
    c = tiny_cell("bad51.drain")
    d = generator.make(c.cfg, c.cfg["execution_records"],
                       c.traffic["pool_executions"], 21)
    raw = reference.own_tables(c.cfg, d.sub_params, d.sub_brokers,
                               d.user_locs.shape[0])
    sids = {ch["name"]: np.arange(d.sub_params.size)
            for ch in c.cfg["channels"]}
    ctx, _ = reference.check_tables(c.cfg, d.sub_params, d.sub_brokers, sids,
                                    raw, d.user_locs, d.user_brokers)
    drv = control.stand_in(c, ctx, raw, d, 21, 8, ())
    r = check.compare(ctx, d, drv, 0)
    assert r.correct, r.values
    assert drv.replays and all(e.wire for e in drv.replays)


def test_own_tables_pass_the_table_check():
    c = tiny_cell("bad51.drain")
    d = generator.make(c.cfg, 1024, 1, 5)
    raw = reference.own_tables(c.cfg, d.sub_params, d.sub_brokers,
                               d.user_locs.shape[0])
    sids = {ch["name"]: np.arange(d.sub_params.size)
            for ch in c.cfg["channels"]}
    _, off = reference.check_tables(c.cfg, d.sub_params, d.sub_brokers, sids,
                                    raw, d.user_locs, d.user_brokers)
    assert off == 0
    # a subscriber filed under another state's group is caught
    tbl = raw["TweetsAboutDrugs"].copy()
    tbl[[0, -1], 0] = tbl[[-1, 0], 0]
    raw["TweetsAboutDrugs"] = tbl
    _, off = reference.check_tables(c.cfg, d.sub_params, d.sub_brokers, sids,
                                    raw, d.user_locs, d.user_brokers)
    assert off > 0


def test_multiset_off():
    a = np.array([1, 2, 2, 5])
    assert reference.multiset_off(a, a) == 0
    assert reference.multiset_off(a, np.array([1, 2, 5])) == 1
    assert reference.multiset_off(a, np.array([1, 2, 2, 6])) == 2


def test_wire_off_reads_each_fault_of_a_line():
    """One line of record 7 to a group of sIDs 3 and 5, 2 payload words."""
    want = np.array([7 * 10 + 3, 7 * 10 + 5])
    line = np.array([[7, 0, 2, 2, 3, 5, -1, 7, 7]])
    notify = np.array([3, 5])
    assert check.wire_off(line, notify, 3, 10, want) == (0, 0)
    wrong_sid = line.copy()
    wrong_sid[0, 5] = 4
    assert check.wire_off(wrong_sid, notify, 3, 10, want)[0] == 2
    wrong_word = line.copy()
    wrong_word[0, -1] = 8
    assert check.wire_off(wrong_word, notify, 3, 10, want)[0] == 1
    assert check.wire_off(line, np.array([3, 6]), 3, 10, want)[1] == 2
    assert check.wire_off(line, np.array([3]), 3, 10, want)[1] == 2
