"""From a profiler trace of the window to the numbers the metric readers use.

The JAX profiler writes an XSpace (``*.xplane.pb``). ``load`` reads it into
plain dicts of the planes, lines and events this reduction needs, which is
also the form of the small recorded trace its tests keep
(``bench/tests/data``). ``reduce`` then takes, on the same clock:

- the window: the host span ``window`` the loop opens around it;
- device busy time: the union of the op intervals on each chip's
  ``XLA Ops`` line, clipped to the window, averaged over the chips used;
- per-program device time: the ``XLA Modules`` events, by program name
  (``jit_ingest_step``, ``jit_run``), with their counts;
- per-program custom calls: the op events that run inside a program's
  execution and are custom calls (the Pallas kernels);
- the longest idle gaps between busy intervals, each named by the
  benchmark's host span (ingest, step, drain, flush) open in its middle.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import re
from typing import Dict, List, Tuple

HOST_SPANS = ("ingest", "step", "drain", "flush")
WINDOW = "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def options():
    import jax
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 2
    return o


def _op(text: str) -> list:
    """An ``XLA Ops`` event is named by its HLO text, ``%while.120 = (...)
    while(...)``: keep the instruction's name, and whether it is a custom
    call (a Pallas kernel: ``custom-call(..., custom_call_target=
    "tpu_custom_call")``)."""
    return [text.split(" = ", 1)[0].lstrip("%"), "custom-call(" in text]


def load(path: str) -> Dict:
    """The trace's device and host events: ``{"device": {plane: {line:
    [[name, start_ns, dur_ns, custom]]}}, "host": [[name, start_ns,
    dur_ns]]}`` with only the lines and spans the reduction reads."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = {"device": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {}
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    lines[line.name] = [[e.name, e.start_ns, e.duration_ns,
                                         False] for e in line.events]
                elif line.name == OPS_LINE:
                    lines[line.name] = []
                    for e in line.events:
                        name, custom = _op(e.name)
                        lines[line.name].append([name, e.start_ns,
                                                 e.duration_ns, custom])
            out["device"][plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS or e.name == WINDOW:
                        out["host"].append([e.name, e.start_ns,
                                            e.duration_ns])
    return out


def load_json(path: str) -> Dict:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _program(name: str) -> str:
    """``jit_run(1234)`` -> ``jit_run``."""
    return re.sub(r"\(\d+\)$", "", name)


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    modules: Dict[str, List[float]]            # program -> [count, seconds]
    custom: Dict[str, List[float]]             # program -> [count, seconds]
    ops: Dict[str, float]                      # op name -> seconds
    gaps: List[Tuple[str, float]]              # longest idle gaps

    def breakdown(self) -> Dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[n, s] for n, s in self.gaps[:10]]}


def reduce(tr: Dict, chips: int = 1) -> Reduced:
    win = [h for h in tr["host"] if h[0] == WINDOW]
    if not win:
        raise ValueError("trace has no 'window' host span")
    w0 = min(h[1] for h in win)
    w1 = max(h[1] + h[2] for h in win)
    spans = sorted((h[1], h[1] + h[2], h[0]) for h in tr["host"]
                   if h[0] in HOST_SPANS)
    planes = sorted(tr["device"])[:chips]
    busy = 0.0
    modules: Dict[str, List[float]] = {}
    custom: Dict[str, List[float]] = {}
    ops: Dict[str, float] = {}
    holes: List[Tuple[float, float]] = []
    for plane in planes:
        lines = tr["device"][plane]
        mods = sorted((max(s, w0), min(s + d, w1), _program(n))
                      for n, s, d, _ in lines.get(MODULES_LINE, [])
                      if s < w1 and s + d > w0)
        for s, e, name in mods:
            m = modules.setdefault(name, [0, 0.0])
            m[0] += 1
            m[1] += (e - s) * 1e-9
        starts = [m[0] for m in mods]
        iv = []
        for n, s, d, is_custom in lines.get(OPS_LINE, []):
            s, e = max(s, w0), min(s + d, w1)
            if e <= s:
                continue
            iv.append((s, e))
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][2] if i >= 0 and s < mods[i][1] else "none"
            key = f"{prog}:{n}"
            ops[key] = ops.get(key, 0.0) + (e - s) * 1e-9
            if is_custom:
                c = custom.setdefault(prog, [0, 0.0])
                c[0] += 1
                c[1] += (e - s) * 1e-9
        u = _union(iv)
        busy += sum(e - s for s, e in u) * 1e-9
        edges = [w0] + [x for se in u for x in se] + [w1]
        holes += [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    n = max(len(planes), 1)
    holes.sort(key=lambda h: h[0] - h[1])
    gaps = [(_span_at(spans, (a + b) / 2), (b - a) * 1e-9)
            for a, b in holes[:10]]
    return Reduced((w1 - w0) * 1e-9, busy / n, modules, custom, ops, gaps)


def _span_at(spans: List[Tuple[float, float, str]], t: float) -> str:
    """The innermost benchmark host span open at ``t``, or ``idle``."""
    best = None
    for s, e, name in spans:
        if s > t:
            break
        if e >= t and (best is None or s >= best[0]):
            best = (s, name)
    return best[1] if best else "idle"


def load_dir(d: str) -> Dict:
    """``load`` the one trace the profiler wrote under ``d``."""
    paths = glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {d}")
    return load(paths[0])
