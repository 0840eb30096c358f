"""Plain numpy reference of the BAD channel semantics (``bench/check.py``
compares the engine with it).

Imports nothing of the program. For one execution's records it computes, per
channel, what the engine must report and deliver:

- a param channel (``TweetsAboutDrugs``): records
  that satisfy the fixed conjunction notify every subscriber whose parameter
  equals the record's parameter field; subscribers are chopped into groups of
  at most ``group_cap`` per (parameter, broker) (Algorithm 1), and a result is
  one (record, group) pair;
- a spatial channel (``TweetsAboutCrime5``): records that satisfy the fixed
  conjunction notify every cohort user within ``radius``, by the float32
  squared distance ``dx*dx + dy*dy < radius**2``; a result is one (record,
  user) pair.

The engine's answer names groups by its own slot numbers. ``check_tables``
reads the engine's delivery tables once, proves from the subscriber data
alone that each slot holds one (parameter, broker) key and that every
subscriber sits in exactly one slot, and from then on a (record, slot) pair
is compared as the reference's (record, group) pair.

What the broker stage delivers is compared as (record, sID) notifications:
a param channel's record notifies the sID of every subscriber with its
parameter, a spatial channel's the user id of every cohort user in range.
Each sID belongs to one broker, so the pairs are the deliveries per broker.

``lower`` evaluates the same semantics one precision step down: with
``"fields"`` in it, int16 record fields; with ``"locations"``, bfloat16
locations and distances, computed with ``jax.numpy`` on the default device.
It is the control that the comparison must fail (``bench/control.py``,
``bench/tests/test_control.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

OPS = {"==": np.equal, "!=": np.not_equal, "<": np.less, "<=": np.less_equal,
       ">": np.greater, ">=": np.greater_equal}


def where_mask(fields: np.ndarray, where: List, schema: List[str],
               lower: bool = False) -> np.ndarray:
    """The channel's fixed conjunction over int32 fields (int16 when
    ``lower``: values and constants wrap as a narrowed column would)."""
    ok = np.ones(fields.shape[0], bool)
    for name, op, value in where:
        col = fields[:, schema.index(name)]
        if lower:
            col = col.astype(np.int16)
            value = np.array(value).astype(np.int16)
        ok &= OPS[op](col, value)
    return ok


@dataclasses.dataclass
class ParamTables:
    """One param channel: what the reference knows of its subscribers, and
    the engine's slot layout once ``check_tables`` has proven it."""

    subs_of_param: np.ndarray       # (domain,) subscribers per parameter
    groups: np.ndarray              # (domain, brokers) Algorithm-1 groups
    slot_param: np.ndarray          # (G,) parameter of each slot, -1 free
    slots_of_param: List[np.ndarray]
    sid_by_param: np.ndarray        # subscribers' sIDs, grouped by parameter
    param_start: np.ndarray         # (domain + 1,) group offsets into it
    sid_space: int                  # every sID lies below it


@dataclasses.dataclass
class SpatialTables:
    uid_of_slot: np.ndarray         # (U,) user id of each cohort slot
    sid_space: int                  # the users: a user id is its sID


@dataclasses.dataclass
class Context:
    cfg: Dict
    user_locs: np.ndarray
    user_brokers: np.ndarray
    tables: Dict[str, object]


def algorithm1_groups(params: np.ndarray, brokers: np.ndarray, domain: int,
                      num_brokers: int, cap: int) -> np.ndarray:
    per = np.zeros((domain, num_brokers), np.int64)
    np.add.at(per, (params, brokers), 1)
    return np.ceil(per / cap).astype(np.int64)


def check_tables(cfg: Dict, params: np.ndarray, brokers: np.ndarray,
                 sids: Dict[str, np.ndarray], raw: Dict[str, np.ndarray],
                 user_locs: np.ndarray, user_brokers: np.ndarray):
    """Prove the engine's delivery tables against the subscriber data.

    ``sids[name]`` are the sIDs the engine assigned to subscribers 0..n-1 of
    a param channel, ``raw[name]`` its slot table ((G, cap) sIDs, -1 free)
    or, for a spatial channel, its (U, 1) slot->user table. Returns the
    context and ``table_off``: subscribers or users missing, duplicated or
    sitting in a slot with another (parameter, broker) key."""
    off = 0
    tables: Dict[str, object] = {}
    nb = cfg["brokers"]
    for ch in cfg["channels"]:
        name = ch["name"]
        tbl = np.asarray(raw[name])
        if ch["join"] == "spatial":
            uid = tbl.reshape(tbl.shape[0], -1)[:, 0] if tbl.size else \
                np.arange(user_locs.shape[0], dtype=np.int32)
            got = np.bincount(uid[uid >= 0], minlength=user_locs.shape[0])
            off += int(np.abs(got - 1).sum())
            tables[name] = SpatialTables(uid.astype(np.int64),
                                         user_locs.shape[0])
            continue
        sid = np.asarray(sids[name], np.int64)
        domain = ch["param_domain"]
        p_of_sid = np.full(int(max(sid.max(), tbl.max())) + 1, -1, np.int64)
        b_of_sid = p_of_sid.copy()
        p_of_sid[sid], b_of_sid[sid] = params, brokers
        live = tbl >= 0
        seen = np.bincount(tbl[live], minlength=p_of_sid.size)
        want = np.zeros(p_of_sid.size, np.int64)
        want[sid] = 1
        off += int(np.abs(seen - want).sum())
        p = np.where(live, p_of_sid[np.where(live, tbl, 0)], -1)
        b = np.where(live, b_of_sid[np.where(live, tbl, 0)], -1)
        big = np.iinfo(np.int64).max
        pmin = np.where(live, p, big).min(axis=1)
        pmax = np.where(live, p, -1).max(axis=1)
        bmin = np.where(live, b, big).min(axis=1)
        bmax = np.where(live, b, -1).max(axis=1)
        mixed = (pmin != pmax) | (bmin != bmax)
        off += int(live[mixed & live.any(axis=1)].sum())
        slot_param = np.where(live.any(axis=1), pmax, -1)
        subs_of_param = np.bincount(params, minlength=domain)
        tables[name] = ParamTables(
            subs_of_param=subs_of_param,
            groups=algorithm1_groups(params, brokers, domain, nb,
                                     cfg["group_cap"]),
            slot_param=slot_param,
            slots_of_param=[np.flatnonzero(slot_param == v)
                            for v in range(domain)],
            sid_by_param=sid[np.argsort(params, kind="stable")],
            param_start=np.r_[0, np.cumsum(subs_of_param)],
            sid_space=p_of_sid.size)
    return Context(cfg, user_locs, user_brokers, tables), off


def own_tables(cfg: Dict, params: np.ndarray, brokers: np.ndarray,
               n_users: int) -> Dict[str, np.ndarray]:
    """Delivery tables laid out by the reference itself, in the form
    ``check_tables`` reads (sIDs are subscriber numbers; each (parameter,
    broker) key chopped into groups of ``group_cap``). The control uses them
    where no engine has run."""
    cap = cfg["group_cap"]
    order = np.lexsort((np.arange(params.size), brokers, params))
    key = params[order].astype(np.int64) * cfg["brokers"] + brokers[order]
    first = np.r_[0, np.flatnonzero(np.diff(key)) + 1]
    rank = np.arange(key.size) - np.repeat(first, np.diff(np.r_[first,
                                                              key.size]))
    group = np.cumsum(np.r_[0, (np.diff(key) != 0) | (rank[1:] % cap == 0)])
    tbl = np.full((int(group[-1]) + 1 if key.size else 0, cap), -1,
                  np.int64)
    tbl[group, rank % cap] = order
    out = {}
    for ch in cfg["channels"]:
        out[ch["name"]] = (np.arange(n_users)[:, None]
                           if ch["join"] == "spatial" else tbl)
    return out


@dataclasses.dataclass
class Answer:
    """One channel's answer for one execution."""

    num_results: int
    num_notified: int
    broker_pairs: np.ndarray              # (brokers,) results per broker
    keys: Optional[np.ndarray] = None     # sorted int64 pair keys
    rows: Optional[np.ndarray] = None     # notifying records, by position
    # sorted (record, sID) notification keys, record * sid_space + sID
    sid_keys: Optional[np.ndarray] = None


def _spatial_hits(locs: np.ndarray, users: np.ndarray, radius: float,
                  lower: bool) -> np.ndarray:
    if not lower:
        r2 = np.float32(radius) ** 2
        d = locs[:, None, :] - users[None, :, :]
        return (d[..., 0] * d[..., 0] + d[..., 1] * d[..., 1]) < r2
    import jax.numpy as jnp
    t = jnp.asarray(locs, jnp.bfloat16)[:, None, :]
    u = jnp.asarray(users, jnp.bfloat16)[None, :, :]
    d = t - u
    r2 = jnp.asarray(radius, jnp.bfloat16) ** 2
    return np.asarray((d[..., 0] * d[..., 0] + d[..., 1] * d[..., 1]) < r2)


def _member_sids(tb: ParamTables, rows: np.ndarray, v: np.ndarray
                 ) -> np.ndarray:
    """(record, sID) keys: each record in ``rows`` with each sID of the
    subscribers whose parameter is its ``v``."""
    n = tb.subs_of_param[v]
    first = np.repeat(tb.param_start[v] - (np.cumsum(n) - n), n)
    sids = tb.sid_by_param[first + np.arange(first.size)]
    return np.sort(np.repeat(rows.astype(np.int64), n) * tb.sid_space
                   + sids)


def answers(ctx: Context, fields: np.ndarray, locs: np.ndarray, row0: int,
            want_keys: bool, lower: tuple = (),
            want_sids: bool = False) -> Dict[str, Answer]:
    """Every channel's answer for the execution whose records are
    ``fields``/``locs``, ingested as global rows ``row0 + i``; with
    ``want_sids`` also its (record, sID) notifications."""
    cfg, out = ctx.cfg, {}
    schema, nb = cfg["schema"], cfg["brokers"]
    for ch in cfg["channels"]:
        name, tb = ch["name"], ctx.tables[ch["name"]]
        m = where_mask(fields, ch["where"], schema, "fields" in lower)
        if ch["join"] == "param":
            v = fields[m, schema.index(ch["param_field"])].astype(np.int64)
            ok = tb.subs_of_param[v] > 0
            rows = np.flatnonzero(m)[ok]
            v = v[ok]
            ans = Answer(int(tb.groups[v].sum()),
                         int(tb.subs_of_param[v].sum()),
                         tb.groups[v].sum(axis=0), rows=rows)
            if want_keys:
                g = tb.slot_param.size
                per = [(r + row0) * g + tb.slots_of_param[p]
                       for r, p in zip(rows, v)]
                ans.keys = np.sort(np.concatenate(per)) if per else \
                    np.zeros(0, np.int64)
            if want_sids:
                ans.sid_keys = _member_sids(tb, rows + row0, v)
        else:
            idx = np.flatnonzero(m)
            hit = _spatial_hits(locs[idx], ctx.user_locs, ch["radius"],
                                "locations" in lower)
            r_i, uid = np.nonzero(hit)
            bp = np.bincount(ctx.user_brokers[uid], minlength=nb)
            rows = np.unique(idx[r_i])
            ans = Answer(int(r_i.size), int(r_i.size), bp, rows=rows)
            if want_keys or want_sids:
                nu = ctx.user_locs.shape[0]
                ans.keys = np.sort((idx[r_i] + row0).astype(np.int64) * nu
                                   + uid)
                ans.sid_keys = ans.keys
        out[name] = ans
    return out


def observed_keys(ctx: Context, name: str, pair_rows: np.ndarray,
                  pair_targets: np.ndarray, pair_valid: np.ndarray
                  ) -> np.ndarray:
    """The engine's (record, slot) pairs of one channel as reference keys."""
    tb = ctx.tables[name]
    r = pair_rows[pair_valid].astype(np.int64)
    t = pair_targets[pair_valid].astype(np.int64)
    if isinstance(tb, SpatialTables):
        nu = ctx.user_locs.shape[0]
        return np.sort(r * nu + tb.uid_of_slot[t])
    return np.sort(r * tb.slot_param.size + t)


def multiset_off(a: np.ndarray, b: np.ndarray) -> int:
    """Size of the multiset symmetric difference of two key arrays."""
    if a.size == b.size and np.array_equal(np.sort(a), np.sort(b)):
        return 0
    keys = np.concatenate([a, b])
    if keys.size == 0:
        return 0
    u, inv = np.unique(keys, return_inverse=True)
    ca = np.bincount(inv[:a.size], minlength=u.size)
    cb = np.bincount(inv[a.size:], minlength=u.size)
    return int(np.abs(ca - cb).sum())
