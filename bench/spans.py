"""The engine's own spans in a profiler trace, reduced on the device clock.

The engine marks its work itself (``repro.core.engine``,
``repro.core.broker``, ``repro.core.runtime``):

- device stages: named scopes inside the fused plan-group call,
  ``bad.discover``, ``bad.join``, ``bad.convert``, ``bad.send`` and
  ``bad.ring``. A scope lands in each HLO op's ``op_name`` metadata
  (``jit(run)/bad.send/vmap(jit(searchsorted))/vmap()/while``), which the
  profiler keeps with each program's HLO in its metadata plane; an op event
  names only its instruction and runs inside one program's event;
- host spans: ``jax.profiler.TraceAnnotation`` events whose names start with
  ``bad.`` (``bad.ingest``, ``bad.step``, ``bad.dispatch.*``, ``bad.sync.*``,
  ``bad.flush``);
- counters: arguments of those host spans, ``notify_slots`` and
  ``produced_sids`` on ``bad.sync.report``.

``load`` reads a trace into ``bench/trace.py``'s form, so that its
``reduce`` reads the same numbers from it, and adds: every ``bad.*`` host
event, with its arguments as a fourth element, and ``scopes``, the stage of
each op that has one, keyed ``<module event>:<op>``. ``reduce`` adds to
``trace.reduce``'s numbers per-scope device time (the union of the scope's
op intervals: op times nest), per-span host time and self time, the
counters' sums, idle gaps named by the innermost benchmark and program span
open at their middle (``step/bad.dispatch.args``), and device ops keyed by
their scope (``jit_run/bad.send:while.130``). ``metrics`` turns those into
per-execution numbers.

A trace of a program without these spans reduces to empty scopes, spans and
counters, and ``metrics`` then leaves those numbers out.

    python3 bench/spans.py <trace dir | .xplane.pb | .json.gz> <executions>

prints the reduction as JSON.
"""
from __future__ import annotations

import bisect
import dataclasses
import json
import sys
from typing import Dict, List, Optional, Tuple

if __package__ in (None, ""):
    import pathlib
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import trace as tr  # noqa: E402

PREFIX = "bad."
STAGES = ("bad.discover", "bad.join", "bad.convert", "bad.send", "bad.ring")
# host spans in which the host waits on the device; every other ``bad.*``
# span's self time is host work
WAITS = ("bad.dispatch.bucket_read", "bad.sync.wait")
COUNTERS = ("notify_slots", "produced_sids")
# the XSpace protobuf fields read here (tsl/profiler/protobuf/xplane.proto,
# xla/service/hlo.proto, xla/xla_data.proto): XSpace.planes; XPlane.name,
# .event_metadata, .stat_metadata; XEventMetadata.name, .stats;
# XStatMetadata.name; XStat.metadata_id, .bytes_value; HloProto.hlo_module;
# HloModuleProto.computations; HloComputationProto.instructions;
# HloInstructionProto.name, .metadata; OpMetadata.op_name
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


def scope_of(op_name: str) -> str:
    """The innermost ``bad.*`` component of an ``op_name`` path, or ''."""
    parts = [p for p in op_name.split("/") if p.startswith(PREFIX)]
    return parts[-1] if parts else ""


def _fields(buf: memoryview):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited field; fixed-width fields are
    skipped (none of those read here is one)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _varint(buf: memoryview, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _msg(buf: memoryview, want: int) -> list:
    """Every value of field ``want``."""
    return [v for f, v in _fields(buf) if f == want]


def _text(buf: memoryview, want: int) -> str:
    """The first string of field ``want``, or ''."""
    vals = _msg(buf, want)
    return bytes(vals[0]).decode() if vals else ""


def hlo_scopes(xspace: bytes) -> Dict[str, str]:
    """``<module>:<instruction>`` -> stage, for every instruction of every
    program in the trace whose ``op_name`` carries a ``bad.*`` scope. The
    op events on a TPU carry no ``op_name``; the programs' HLO, which the
    profiler keeps in its metadata plane, does."""
    out: Dict[str, str] = {}
    for plane in _msg(memoryview(xspace), 1):
        if _text(plane, 2) != METADATA_PLANE:
            continue
        hlo_stat = {_msg(e, 1)[0] for e in _msg(plane, 5)
                    if _text(_msg(e, 2)[0], 2) == HLO_PROTO_STAT}
        for entry in _msg(plane, 4):
            meta = _msg(entry, 2)[0]
            module = _text(meta, 2)
            for stat in _msg(meta, 5):
                st = dict(_fields(stat))
                if st.get(1) in hlo_stat and st.get(6) is not None:
                    for mod in _msg(st[6], 1):
                        _module_scopes(module, mod, out)
    return out


def _module_scopes(module: str, hlo_module: memoryview,
                   out: Dict[str, str]) -> None:
    for comp in _msg(hlo_module, 3):
        for ins in _msg(comp, 2):
            meta = _msg(ins, 7)
            scope = scope_of(_text(meta[0], 2)) if meta else ""
            if scope:
                out[f"{module}:{_text(ins, 1)}"] = scope


def _module_at(mods: List[list], starts: List[float], t: float) -> str:
    """The module event (sorted by start) running at ``t``, or ``none``."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < mods[i][1] + mods[i][2]:
        return mods[i][0]
    return "none"


def load(path: str) -> Dict:
    """``trace.load``'s dict, plus the ``bad.*`` host events (``[name,
    start_ns, dur_ns, args]``) in ``host`` and the programs' stages in
    ``scopes`` (``hlo_scopes``)."""
    from jax.profiler import ProfileData
    out = tr.load(path)
    with open(path, "rb") as f:
        out["scopes"] = hlo_scopes(f.read())
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        out["host"].append([e.name, e.start_ns,
                                            e.duration_ns, dict(e.stats)])
    return out


def load_any(path: str) -> Dict:
    """A trace directory, an ``.xplane.pb`` or a recorded ``.json[.gz]``."""
    if path.endswith((".json", ".json.gz")):
        return tr.load_json(path)
    if path.endswith(".xplane.pb"):
        return load(path)
    import glob
    paths = glob.glob(f"{path}/plugins/profile/*/*.xplane.pb")
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {path}")
    return load(paths[0])


@dataclasses.dataclass
class Spans:
    base: tr.Reduced                     # what ``trace.reduce`` reads
    scope_s: Dict[str, float]            # stage -> device seconds (union)
    span_s: Dict[str, List[float]]       # host span -> [count, s, self s]
    counters: Dict[str, int]             # counter -> sum over the window
    ops: Dict[str, float]                # "prog/scope:op" -> seconds
    gaps: List[Tuple[str, float]]        # idle gaps, named by span path

    def breakdown(self) -> Dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[n, s] for n, s in self.gaps[:10]]}


def _clip(s: float, e: float, w0: float, w1: float) -> float:
    return max(0.0, min(e, w1) - max(s, w0))


def _host_spans(tr_: Dict, w0: float, w1: float):
    """``bad.*`` spans in the window: per name [count, seconds, self
    seconds], and the counters summed over the spans that start in it."""
    spans = sorted(((h[1], h[1] + h[2], h[0], h[3] if len(h) > 3 else {})
                    for h in tr_["host"] if h[0].startswith(PREFIX)),
                   key=lambda x: (x[0], -x[1]))
    child = [0.0] * len(spans)
    stack: List[int] = []
    for i, (s, e, _, _) in enumerate(spans):
        while stack and spans[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= spans[stack[-1]][1]:
            child[stack[-1]] += _clip(s, e, w0, w1)
        stack.append(i)
    out: Dict[str, List[float]] = {}
    counters: Dict[str, int] = {}
    for (s, e, name, args), c in zip(spans, child):
        d = _clip(s, e, w0, w1)
        if d <= 0:
            continue
        acc = out.setdefault(name, [0, 0.0, 0.0])
        acc[0] += 1
        acc[1] += d * 1e-9
        acc[2] += (d - c) * 1e-9
        if w0 <= s < w1:
            for k in COUNTERS:
                if k in args:
                    counters[k] = counters.get(k, 0) + int(args[k])
    return out, counters


def _path_at(bench: List[tuple], prog: List[tuple], t: float) -> str:
    """The innermost benchmark span and program span open at ``t``,
    joined by ``/``; ``idle`` where neither is."""
    names = [n for n in (tr._span_at(bench, t), tr._span_at(prog, t))
             if n != "idle"]
    return "/".join(names) or "idle"


def reduce(tr_: Dict, chips: int = 1) -> Spans:
    base = tr.reduce(tr_, chips)
    win = [h for h in tr_["host"] if h[0] == tr.WINDOW]
    w0 = min(h[1] for h in win)
    w1 = max(h[1] + h[2] for h in win)
    scopes = tr_.get("scopes", {})
    by_scope: Dict[str, List[Tuple[float, float]]] = {}
    ops: Dict[str, float] = {}
    holes: List[Tuple[float, float]] = []
    for plane in sorted(tr_["device"])[:chips]:
        lines = tr_["device"][plane]
        mods = sorted(([n, s, d] for n, s, d, _ in
                       lines.get(tr.MODULES_LINE, [])), key=lambda m: m[1])
        starts = [m[1] for m in mods]
        iv = []
        for n, s, d, _ in lines.get(tr.OPS_LINE, []):
            a, b = max(s, w0), min(s + d, w1)
            if b <= a:
                continue
            iv.append((a, b))
            mod = _module_at(mods, starts, s)
            scope = scopes.get(f"{mod}:{n}", "")
            if scope:
                by_scope.setdefault(scope, []).append((a, b))
            key = tr._program(mod) + (f"/{scope}" if scope else "") + f":{n}"
            ops[key] = ops.get(key, 0.0) + (b - a) * 1e-9
        u = tr._union(iv)
        edges = [w0] + [x for se in u for x in se] + [w1]
        holes += [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    n = max(min(chips, len(tr_["device"])), 1)
    scope_s = {k: sum(b - a for a, b in tr._union(v)) * 1e-9 / n
               for k, v in by_scope.items()}
    span_s, counters = _host_spans(tr_, w0, w1)
    bench = sorted((h[1], h[1] + h[2], h[0]) for h in tr_["host"]
                   if h[0] in tr.HOST_SPANS)
    # spans that start together: the innermost, the shortest, sorts last
    prog = sorted(((h[1], h[1] + h[2], h[0]) for h in tr_["host"]
                   if h[0].startswith(PREFIX)), key=lambda x: (x[0], -x[1]))
    holes.sort(key=lambda h: h[0] - h[1])
    gaps = [(_path_at(bench, prog, (a + b) / 2), (b - a) * 1e-9)
            for a, b in holes[:10]]
    return Spans(base, scope_s, span_s, counters, ops, gaps)


def metrics(sp: Spans, executions: int) -> Dict[str, float]:
    """Per-execution numbers of one traced window; a number whose spans or
    counters the trace lacks is left out."""
    out: Dict[str, float] = {}
    if executions <= 0:
        return out
    for stage in STAGES:
        if stage in sp.scope_s:
            out[f"{stage[len(PREFIX):]}.device_ms"] = (
                sp.scope_s[stage] / executions * 1e3)
    slots = sp.counters.get("notify_slots", 0)
    if slots:
        out["send_slot_use_pct"] = (
            100.0 * sp.counters.get("produced_sids", 0) / slots)
    if sp.span_s:
        wait = sum(sp.span_s[k][1] for k in WAITS if k in sp.span_s)
        work = sum(v[2] for k, v in sp.span_s.items() if k not in WAITS)
        out["device_wait_ms"] = wait / executions * 1e3
        out["host_work_ms"] = work / executions * 1e3
    return out


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 2:
        print("usage: python3 bench/spans.py <trace dir | .xplane.pb | "
              ".json.gz> <executions>", file=sys.stderr)
        return 2
    sp = reduce(load_any(argv[0]))
    print(json.dumps({
        "metrics": metrics(sp, int(argv[1])),
        "scope_s": sp.scope_s, "span_s": sp.span_s,
        "counters": sp.counters, "breakdown": sp.breakdown(),
        "jit_run_s": sp.base.modules.get("jit_run", [0, 0.0])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
