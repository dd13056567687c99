"""One run of one benchmark cell: build, warm up, serve a timed window on the
wall clock, check the answers against the reference, report.

Everything specific to a cell is data found by name: the cell in
``BENCHMARK.json``, its configuration ``bench/configs/<config>.json``, its
traffic ``bench/traffic/<traffic>.json`` and one reader per per-layer metric
``bench/metrics/<metric>.py``.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace
from typing import Callable, List, Optional

import numpy as np

from . import gen, load, reference, templates, trace_reduce, work
from .pump import ClosedLoop, OpenLoop

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def manifest(root: str = ROOT) -> dict:
    return load_json(root, "BENCHMARK.json")


def cell_files(man: dict, workload: str, root: str = ROOT):
    """(cell, configuration, traffic) of a workload, found by name."""
    cells = {c["name"]: c for c in man["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in man["configs"]}[cell["config"]]
    cfg = load_json(root, cfg_entry["file"])
    traffic = load_json(root, "bench", "traffic", cell["traffic"] + ".json")
    if traffic["loop"]["kind"] not in load.LOOPS:
        raise ValueError(f"traffic {cell['traffic']}: no loop "
                         f"{traffic['loop']['kind']!r}")
    unknown = set(load.weights(traffic)) - set(cfg["templates"])
    if unknown:
        raise ValueError(f"traffic {cell['traffic']} sends {sorted(unknown)}, "
                         f"which {cell['config']} does not serve")
    return cell, cfg, traffic


def mode_of(cfg: dict) -> str:
    return cfg["scheduler"].get("mode", "static")


def buckets_of(cfg: dict) -> int:
    return int(cfg["scheduler"].get("n_buckets", 16))


def metrics_of(man: dict, workload: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
    that list it, or list no cells (per-layer ones then where the
    end-to-end metric they move is reported)."""
    e2e = [m for m in man["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in man["per_layer"]
            if workload in m.get("workloads", [workload])
            and m["moves"] in names]


def reader(name: str) -> Callable:
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileClock:
    """Counts JAX's backend compiles and persistent-cache hits."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.n_compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.n_compiles += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def programs(self) -> int:
        """Programs made ready so far: compiled or read from the cache."""
        return self.n_compiles + self.cache_hits


# --------------------------------------------------------------- set-up
def make_scheduler(cfg: dict, tg):
    """The served path: ``BatchScheduler`` with the configuration's
    ``scheduler`` options as they stand (``mode`` by name), answers kept,
    every query admitted, the planner's default coefficients."""
    from repro.core import engine as E
    from repro.core.planner import DEFAULT_COEFFS
    from repro.serving import BatchScheduler

    opts = dict(cfg["scheduler"])
    opts["mode"] = {"static": E.MODE_STATIC, "bucket": E.MODE_BUCKET,
                    "interval": E.MODE_INTERVAL}[mode_of(cfg)]
    return BatchScheduler(tg, keep_outputs=True, coeffs=dict(DEFAULT_COEFFS),
                          **opts)


def warm_up(sched, cfg: dict, traffic: dict, g: gen.RawGraph) -> list:
    """Run every (template, padded batch size) the window can dispatch
    once, from parameters that do not depend on the run's seed (the first
    run of each compiles it or reads it from the persistent cache)."""
    from . import system

    rng = np.random.default_rng(int(traffic["warm_seed"]))
    pool = templates.pools(g)
    cap = int(cfg["max_batch_per_group"])
    sizes = sorted({1 << (b - 1).bit_length() for b in range(1, cap + 1)})
    rows = []
    for name in load.weights(traffic):
        for b in sizes:
            for _ in range(b):
                sched.submit(system.to_query(templates.draw(name, rng, pool)))
            out = sched.flush()
            bad = [r for r in out if r.status != "done"]
            if bad:
                raise RuntimeError(f"warm-up {name} batch {b}: "
                                   f"{bad[0].status}: {bad[0].error}")
            d = sched.last_dispatches[0]
            rows.append((name, b, d.split, d.service_s))
    return rows


def percentile(x, q: float) -> float:
    x = np.asarray(x, np.float64)
    return float(np.percentile(x, q)) if x.size else float("nan")


E2E = {
    "latency_p50_ms": lambda w: percentile(w.lat_ms, 50),
    "latency_p95_ms": lambda w: percentile(w.lat_ms, 95),
    "throughput_qps": lambda w: w.n_done / w.span_s,
    "setup_s": lambda w: w.setup_s,
}


def prepare(cfg: dict, traffic: dict, seed: int, clock: CompileClock):
    """Set-up: the graph from the seed, the scheduler over it, warm-up.
    Returns (benchmark graph, scheduler)."""
    from . import system

    t = time.perf_counter()
    g = gen.generate(cfg["graph"], seed)
    log(f"graph: {g.n_vertices} vertices, {g.n_edges} edges, generated in "
        f"{time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    sched = make_scheduler(cfg, system.to_graph(g))
    log(f"scheduler built in {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    rows = warm_up(sched, cfg, traffic, g)
    log(f"warm-up: {len(rows)} dispatches in {time.perf_counter() - t:.3f} s, "
        f"{clock.n_compiles} compiles ({clock.seconds:.3f} s), "
        f"{clock.cache_hits} persistent-cache hits")
    for name, b, split, s in rows:
        log(f"warm-up {name} batch {b} split {split}: {s * 1e3:.3f} ms")
    return g, sched


# ------------------------------------------------------------------ a run
def make_loop(cfg: dict, traffic: dict, g: gen.RawGraph, sched, seed: int,
              seconds: float, span=None, on_flush=None):
    """The window's load, as the traffic file says: (loop, plain queries
    drawn so far: the whole window for an open loop, filled as they are
    sent for a closed one)."""
    from . import system

    kw = dict(on_flush=on_flush, **({"span": span} if span else {}))
    cap, grace = cfg["max_batch_per_group"], traffic["drain_grace_s"]
    lp = traffic["loop"]
    if lp["kind"] == "open":
        plain, due = load.open_window(traffic, g, seed, seconds)
        queries = [system.to_query(q) for q in plain]
        return OpenLoop(sched, queries, due, [q["template"] for q in plain],
                        cap, grace, **kw), plain
    stream, plain = load.ClosedStream(traffic, g, seed), []

    def make(i, at):
        plain.append(stream.next(at))
        return system.to_query(plain[i]), plain[i]["template"]

    return ClosedLoop(sched, make, lp["clients"], lp.get("think_s", 0.0),
                      seconds, cap, grace, **kw), plain


def check_answers(cfg: dict, seed: int, plain: list, w, workers: int):
    """Every query due in the window against the reference: (mismatched,
    unanswered, checked, non-zero among the checked)."""
    from . import system

    items, missing = [], 0
    for i, q in enumerate(plain):
        r = w.answers[i]
        if r is None or w.status[i] != "done":
            missing += 1
            log(f"check: query {i} ({q['template']}) not answered: "
                f"{w.status[i]}")
            continue
        items.append((q, reference.sparse(system.served_answer(r))))
    got = reference.check_many(cfg["graph"], seed, mode_of(cfg),
                               buckets_of(cfg), items, workers=workers)
    mismatched = 0
    for (q, served), (ok, _) in zip(items, got):
        if not ok:
            mismatched += 1
            log(f"check: {q['template']} served {served['total'][:4]}, the "
                f"reference disagrees")
    return mismatched, missing, len(items), sum(nz for _, nz in got)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_process: float, root: str = ROOT, require_tpu: bool = True,
             compile_cache: bool = True, workers: Optional[int] = None,
             after_warmup: Optional[Callable] = None) -> Optional[dict]:
    """One run; returns the result line's object (None: no result).
    Tests on the CPU pass ``require_tpu=False`` and ``compile_cache=False``,
    and ``after_warmup(sched)`` may plant a fault in the timed path."""
    man = manifest(root)
    cell, cfg, traffic = cell_files(man, workload, root)
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        log(f"FAIL: JAX's first device is {devs[0].platform}, not a TPU; "
            f"the benchmark never runs on the CPU")
        return None
    if len(devs) < cell["chips"]:
        log(f"FAIL: the cell needs {cell['chips']} chips, JAX sees "
            f"{len(devs)}")
        return None
    peaks = load_json(BENCH, "peaks.json")
    kind = devs[0].device_kind
    if require_tpu and kind not in peaks:
        log(f"FAIL: no peaks for device kind {kind!r} in bench/peaks.json")
        return None
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = "off"
    if compile_cache:
        cache_dir = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"device {devs[0].platform} {kind} x{len(devs)}; jax "
        f"{jax.__version__}; compile cache {cache_dir}")
    clock = CompileClock()
    g, sched = prepare(cfg, traffic, seed, clock)
    if after_warmup is not None:
        after_warmup(sched)

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    tstate = dict(k0=None, k1=None, on=False, t=0.0)

    def on_flush(k, phase):
        """Trace whole flushes: from the first that starts
        ``trace_after_s`` into the window until ``trace_min_s`` are
        traced."""
        if not trace:
            return
        now = time.perf_counter()
        if (phase == "start" and tstate["k0"] is None
                and now - t0 >= traffic["trace_after_s"]):
            tstate.update(k0=k, on=True, t=now)
            jax.profiler.start_trace(trace_dir)
        elif (phase == "end" and tstate["on"]
              and now - tstate["t"] >= traffic["trace_min_s"]):
            jax.profiler.stop_trace()
            tstate.update(on=False, k1=k + 1)

    span = jax.profiler.TraceAnnotation if trace else None
    loop, plain = make_loop(cfg, traffic, g, sched, seed, seconds, span,
                            on_flush)
    gc.collect()
    gc.freeze()
    programs0 = clock.programs()
    t0 = time.perf_counter()
    setup_s = t0 - t_process
    try:
        w = loop.run(t0)
    finally:
        if tstate["on"]:
            jax.profiler.stop_trace()
            tstate["on"] = False
    window_compiles = clock.programs() - programs0
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs[:cell["chips"]])
    log(f"device peak_bytes_in_use {peak}")

    done = np.isfinite(w.done_t)
    lat_ms = (w.done_t[done] - (t0 + w.due[done])) * 1e3
    n_done = int(done.sum())
    last = float(np.nanmax(w.done_t)) if n_done else t0 + 1e-9
    summary = SimpleNamespace(lat_ms=lat_ms, n_done=n_done,
                              span_s=last - t0, setup_s=setup_s)
    log(f"window: {len(plain)} due in {seconds:g} s, {n_done} done, "
        f"{len(w.flushes)} flushes, {window_compiles} programs compiled or "
        f"loaded in the window")

    # ---- per-layer readings
    tr = None
    if trace:
        tr = trace_reduce.reduce(trace_reduce.load(
            trace_reduce.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
    flushes = w.flushes
    traced = ([] if tstate["k0"] is None else
              flushes[tstate["k0"]:tstate["k1"] or len(flushes)])
    width = 1 if mode_of(cfg) == "static" else buckets_of(cfg)

    def disp_rows(fs):
        out = []
        for f in fs:
            for d in f.dispatches:
                q = plain[f.queries[d.indices[0]]]
                out.append(SimpleNamespace(service_s=d.service_s,
                                           n_real=d.n_real, n_pad=d.n_pad,
                                           template=q["template"],
                                           hops=len(q["e"])))
        return out

    traced_rows = disp_rows(traced)
    ops = nbytes = 0
    for d in traced_rows:
        o, b = work.hop_work(g.n_vertices, g.n_edges, width, d.hops, d.n_real)
        ops += o
        nbytes += b
    ctx = SimpleNamespace(
        late_s=w.late_s, flushes=flushes, dispatches=disp_rows(flushes),
        window_compiles=window_compiles, trace=tr, traced=traced_rows,
        least_time_s=(work.least_time_s(ops, nbytes, peaks[kind])
                      if traced_rows and kind in peaks else None))

    # ---- the answers against the reference, the program's state freed
    del loop, sched
    gc.unfreeze()
    gc.collect()
    t = time.perf_counter()
    workers = reference.default_workers() if workers is None else workers
    log(f"reference: {workers} workers, "
        f"{(reference.available_bytes() or 0) / 2**30:.1f} GiB available")
    mismatched, missing, checked, nonzero = check_answers(cfg, seed, plain, w,
                                                          workers)
    log(f"reference checked {checked} answers ({nonzero} non-zero) in "
        f"{time.perf_counter() - t:.3f} s")
    nz_floor = -(-checked * int(traffic.get("nonzero_percent_min", 0)) // 100)

    metrics = {}
    if trace:
        for m in metrics_of(man, workload, "per_layer"):
            v = reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = dict(value=float(v), unit=m["unit"])
    else:
        for m in metrics_of(man, workload, "end_to_end"):
            metrics[m["name"]] = dict(value=float(E2E[m["name"]](summary)),
                                      unit=m["unit"])
    device = dict(platform=devs[0].platform, kind=kind, count=len(devs),
                  memory_peak_bytes=peak)
    checks = dict(mismatched=dict(value=mismatched, limit=0, ok="<="),
                  unanswered=dict(value=missing, limit=0, ok="<="),
                  nonzero_checked=dict(value=nonzero, limit=nz_floor,
                                       ok=">="))
    out = dict(correct=(mismatched == 0 and missing == 0
                        and nonzero >= nz_floor),
               attempted=len(plain), failed=len(plain) - n_done,
               metrics=metrics, device=device)
    if tr is not None:
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        out["breakdown"] = dict(device_ops=tr["device_ops"],
                                idle_gaps=tr["idle_gaps"])
    out["checks"] = checks
    return out
