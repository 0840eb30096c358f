"""Tiny sizes for running the cells on the CPU: every shape kind kept, every
count cut, so that a test drives the whole harness in seconds."""
from __future__ import annotations

import copy
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

CONFIG = {
    "preload_records": 2048, "preload_batch": 512, "execution_records": 1024,
    "subscribers": 4000, "frame_bytes": 256, "group_cap": 64,
}
ENGINE = {"dataset_capacity": 1 << 14, "index_capacity": 1 << 14,
          "max_deliver_pairs": 1 << 10, "max_notify": 1 << 15}
TRAFFIC = {"drain": {"pool_executions": 4},
           "alerts": {"period_s": 0.25}}


def tiny_cell(name: str):
    """``run.cell(name)`` with the tiny sizes in place of the published."""
    from bench import run
    c = run.cell(name)
    cfg = copy.deepcopy(c.cfg)
    cfg.update(CONFIG)
    if cfg.get("located_users"):
        cfg["located_users"] = 2048
    cfg["engine"].update(ENGINE)
    c.cfg = cfg
    c.traffic = dict(c.traffic, **TRAFFIC[c.traffic["loop"] == "open"
                                          and "alerts" or "drain"])
    return c
