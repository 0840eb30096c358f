"""Run one benchmark cell once on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json``, ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); its metrics are read by the readers in
``bench/metrics/<metric>.py``. With ``--trace 0`` the last stdout line carries
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
from a profiler trace of the same window.

Set-up (data from the seed, preload, subscribers, warm-up of every shape the
window uses) is timed as ``setup_s``; the window then runs for ``--seconds``
with nothing left to compile. After it, the peak device bytes are read, the
rings and the spill queue are drained, a few sampled executions are run
again with their delivery buffers copied to the host, and the engine is
freed. Then every execution's counts and delivery stats, the pair sets of a
seeded sample of executions and the delivered wire buffers of the replayed
ones are compared with the plain reference (``bench/reference.py``).

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits with code 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, Optional  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def load_json(path: pathlib.Path) -> Dict:
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: Dict
    traffic: Dict
    end_to_end: Dict[str, Dict]      # metric name -> BENCHMARK.json entry
    per_layer: Dict[str, Dict]


def cell(name: str, bench: Optional[Dict] = None) -> Cell:
    """A cell and its metrics, found by name in ``BENCHMARK.json``."""
    bench = bench or load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def mine(m: Dict, reported=None) -> bool:
        if "workloads" in m:
            return name in m["workloads"]
        return reported is None or m["moves"] in reported

    e2e = {m["name"]: m for m in bench["end_to_end"] if mine(m)}
    layer = {m["name"]: m for m in bench["per_layer"] if mine(m, e2e)}
    return Cell(name, w["chips"], load_json(ROOT / conf["file"]),
                load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                e2e, layer)


def reader(metric: str):
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read of one run."""

    loop: str
    setup_s: float
    window: tuple                    # host clock (first ingest, last sync)
    execs: list
    latencies_ms: list               # open loop: per notifying record
    trace: object = None             # bench.trace.Reduced, --trace 1 only
    device_kind: str = ""
    cfg: Optional[Dict] = None

    @property
    def records(self) -> int:
        return sum(e.records for e in self.execs)


def enable_cache() -> str:
    import jax
    from repro.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    # every program the window runs comes from the cache in later runs,
    # also the ones that compile in under the default one second
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_cell(c: Cell, seed: int, seconds: float, trace: bool,
             t_start: float = T_START) -> Dict:
    import jax
    import numpy as np

    from bench import check, compiles, deploy, generator, loops, reference
    from bench import trace as tr

    cfg, traffic = c.cfg, c.traffic
    cache = enable_cache()
    loop_cls, per_exec, n_batches = loops.plan(cfg, traffic, seconds)
    with compiles.watch_compiles() as setup_log:
        data = generator.make(cfg, per_exec, n_batches, seed)
        eng, sids = deploy.build(cfg, data)
        drv = loop_cls(eng, cfg, traffic, data, seed)
        drv.warm()
        jax.block_until_ready(eng.dataset.fields)
    setup_s = time.perf_counter() - t_start

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    traces0 = eng.maintenance.traces
    with compiles.watch_compiles() as win_log:
        if trace:
            jax.profiler.start_trace(trace_dir, profiler_options=tr.options())
        t0, t1 = drv.window(seconds)
        if trace:
            jax.profiler.stop_trace()
    retraces = eng.maintenance.traces - traces0
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", 0)
    drv.finish()
    drv.replay()
    tables = deploy.delivery_tables(eng, cfg)
    del eng, drv.eng
    gc.collect()

    ctx, table_off = reference.check_tables(
        cfg, data.sub_params, data.sub_brokers, sids, tables,
        data.user_locs, data.user_brokers)
    result = check.compare(ctx, data, drv, table_off)
    lat = check.latencies_ms(ctx, data, drv) if traffic["loop"] == "open" \
        else []
    reduced = None
    if trace:
        reduced = tr.reduce(tr.load_dir(trace_dir), c.chips)
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = Run(traffic["loop"], setup_s, (t0, t1), drv.execs, lat, reduced,
              dev.device_kind, cfg)

    wanted = c.per_layer if trace else c.end_to_end
    metrics = {}
    for name, m in wanted.items():
        value = reader(name)(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": m["unit"]}

    late = drv.lateness
    print(f"cell {c.name} seed {seed}: {len(drv.execs)} executions, "
          f"{run.records} records in {t1 - t0:.6f} s; setup {setup_s:.6f} s "
          f"(programs made ready: {setup_log.programs}, of them "
          f"{setup_log.cache_hits} from the compile cache, "
          f"{setup_log.seconds:.3f} s; cache {cache})")
    print(f"window compiles: {win_log.programs} programs, "
          f"{win_log.cache_hits} cache hits, {retraces} engine retraces")
    print(f"peak_bytes_in_use: {peak}")
    print(f"notifications produced {result.attempted}, failed "
          f"{result.failed}; notifying records {result.notifying}; "
          f"wire buffers compared for {len(drv.replays)} replayed executions")
    if late:
        print(f"generator lateness s: median {float(np.median(late)):.6f} "
              f"max {max(late):.6f} over {len(late)} executions")
    gaps = np.diff([e.done for e in drv.execs])
    if gaps.size:
        print(f"seconds between materialisations: median "
              f"{float(np.median(gaps)):.6f}, max {float(gaps.max()):.6f} "
              f"before execution {int(gaps.argmax()) + 1}")
    for line in result.lines():
        print(line, file=sys.stderr)

    out = {"correct": result.correct, "attempted": result.attempted,
           "failed": result.failed, "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": c.chips, "memory_peak_bytes": int(peak)}}
    if trace:
        out["device"]["busy_s"] = reduced.busy_s
        out["device"]["window_s"] = reduced.window_s
        out["breakdown"] = reduced.breakdown()
    out["checks"] = result.as_dict()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    c = cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < c.chips:
        print(f"bench: cell {c.name} needs {c.chips} TPU chip(s), found "
              f"{devices}; refusing to run", file=sys.stderr)
        return 2
    out = run_cell(c, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
