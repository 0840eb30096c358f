"""Build a configuration's engine through the program's public surface.

What the benchmark calls on the program, and nothing else (PERF.md lists it
so that a change to it is known to change the yardstick):
``BADEngine(**kwargs)``, ``ChannelSpec``, ``Predicate.parse``,
``RecordBatch.from_numpy``, ``create_channel``, ``subscribe_bulk``,
``set_user_locations``, ``subscribe_users``, ``set_plan(ChannelPlan(...))``,
``ingest``, ``TickPipeline(...).step/flush/drain_due``, ``drain_spilled``,
``flush_rings``, ``spill.pending_pairs/pending_sids``, ``fused_sids_table``,
``maintenance.traces``, ``debug_delivery_buffers`` (set after the window
only) and the reports' counts, ``result`` pair arrays, ``DeliveryStats``
and ``payload``/``notify`` wire buffers.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from repro.core import records as R
from repro.core.channel import ChannelSpec
from repro.core.engine import BADEngine
from repro.core.plans import ChannelPlan
from repro.core.predicates import Predicate


def channel_spec(ch: Dict, schema) -> ChannelSpec:
    preds = tuple(Predicate.parse(schema.index(f), op, v)
                  for f, op, v in ch["where"])
    if ch["join"] == "spatial":
        return ChannelSpec(name=ch["name"], fixed_preds=preds, join="spatial",
                           spatial_radius=float(ch["radius"]),
                           payload_bytes=ch["payload_bytes"])
    return ChannelSpec(name=ch["name"], fixed_preds=preds, join="param",
                       param_field=schema.index(ch["param_field"]),
                       param_domain=ch["param_domain"],
                       payload_bytes=ch["payload_bytes"])


def batch(fields: np.ndarray, locs: np.ndarray) -> R.RecordBatch:
    return R.RecordBatch.from_numpy(fields, locs)


def build(cfg: Dict, data) -> tuple:
    """Preload, then channels, subscribers and plans (a BAD index serves the
    records ingested after its channel exists, as in the paper). Returns the
    engine and the sIDs assigned to each param channel's subscribers."""
    eng = BADEngine(frame_bytes=cfg["frame_bytes"],
                    brokers=tuple(f"Broker{i + 1}"
                                  for i in range(cfg["brokers"])),
                    **cfg["engine"])
    n, step = cfg["preload_records"], cfg["preload_batch"]
    for i in range(0, n, step):
        eng.ingest(batch(data.preload_fields[i:i + step],
                         data.preload_locs[i:i + step]))
    schema = cfg["schema"]
    if data.user_locs.shape[0]:
        eng.set_user_locations(data.user_locs, data.user_brokers)
    sids = {}
    plan = ChannelPlan(**cfg["plan"])
    for ch in cfg["channels"]:
        eng.create_channel(channel_spec(ch, schema))
    for ch in cfg["channels"]:
        if ch["join"] == "spatial":
            eng.subscribe_users(ch["name"], np.arange(
                data.user_locs.shape[0], dtype=np.int32))
        else:
            sids[ch["name"]] = eng.subscribe_bulk(
                ch["name"], data.sub_params, data.sub_brokers)
        eng.set_plan(ch["name"], plan)
    return eng, sids


def delivery_tables(eng, cfg: Dict) -> Dict[str, np.ndarray]:
    aggregated = cfg["plan"].get("aggregation", True)
    return {ch["name"]: np.asarray(eng.fused_sids_table(ch["name"],
                                                        aggregated))
            for ch in cfg["channels"]}
