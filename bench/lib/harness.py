"""One run of one cell: set-up, warm-up, the timed window, the check.

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in the file that entry names, its
traffic in ``bench/traffic/<traffic>.json`` and each metric's reader in
``bench/metrics/<metric>.py``.  The engine gets only what a user gives
it: the relation, the attributes, ``d_f``, ``alpha``, the seed, a query
cache where the configuration has one, and the queries.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import os
import shutil
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench.lib import queries as Q
from bench.lib import reference as ref
from bench.lib.clock import CompileClock, Spans
from bench.lib.data import relation

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
OUT_DIR = os.path.join(ROOT, ".bench_out")
# traced slice of the build (built_in_thread): kept short, since on a
# v5e a 4 s slice open while the build ran its DLV scans made the
# build 2.6-12 times slower
BUILD_TRACE_S = 1.0
PROBES = 2048              # sampled rows whose split-tree route is checked


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: Dict[str, List[dict]]     # "end_to_end" / "per_layer"


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> Cell:
    bm = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bm["configs"]}[w["config"]]
    mets = {kind: [m for m in bm[kind]
                   if name in m.get("workloads", [name])]
            for kind in ("end_to_end", "per_layer")}
    return Cell(name, int(w["chips"]),
                _json(os.path.join(root, cfg["file"])),
                _json(os.path.join(root, "bench", "traffic",
                                   w["traffic"] + ".json")), mets)


def _module(path: str, tag: str):
    spec = importlib.util.spec_from_file_location(tag, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str) -> Callable[[dict], Optional[float]]:
    path = os.path.join(BENCH, "metrics", metric + ".py")
    return _module(path, "bench_metric_" + metric.replace(".", "_")
                   .replace("-", "_")).read


def setup_jax():
    """JAX with its persistent compilation cache at the checkout's fixed
    ``.jax_cache/``, every executable cached, and the engine's x64 on."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    import repro.core  # noqa: F401  (x64 on, as the engine runs)
    return jax


class Profiler:
    """Traces slices of a run, one profiler session each, under ``root``
    (emptied first: only the newest run's traces stay on disk)."""

    def __init__(self, root: str):
        self.root = root
        self.slices: List[tuple] = []      # (name, log dir, seconds)
        shutil.rmtree(root, ignore_errors=True)

    def start(self, name: str) -> None:
        import jax
        self._cur = (name, os.path.join(self.root, name))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host spans and device ops only
        jax.profiler.start_trace(self._cur[1], profiler_options=opts)
        self._t0 = time.perf_counter()

    def stop(self) -> None:
        import jax
        t1 = time.perf_counter()
        jax.profiler.stop_trace()
        self.slices.append(self._cur + (t1 - self._t0,))


def built_in_thread(fn: Callable[[], object], prof: Optional[Profiler],
                    clock: CompileClock, limit_s: float):
    """fn() on a thread of its own, traced or not, while this thread
    waits.  With ``prof``, ``limit_s`` seconds of it are traced from the
    first executable JAX builds for it (compiled, or loaded from the
    cache) on: the device has work from then, and a cold compile before
    it would otherwise fill the slice.  The profiler is started and
    stopped on this thread."""
    box: dict = {}

    def work():
        try:
            box["out"] = fn()
        except BaseException as e:   # handed to the caller below
            box["err"] = e
    built = clock.read()[1]
    th = threading.Thread(target=work, name="bench-build")
    th.start()
    if prof is not None:
        while th.is_alive() and clock.read()[1] == built:
            th.join(0.01)
        if th.is_alive():
            prof.start("build")
            th.join(limit_s)
            prof.stop()
    th.join()
    if "err" in box:
        raise box["err"]
    return box["out"]


class Stream:
    """One client's queries, planned from the seed and made on demand
    (set-up makes the first ``ahead``)."""

    def __init__(self, traffic: dict, template: dict, stats: dict,
                 rng: np.random.Generator, ahead: int = 1024,
                 set_rng: Optional[np.random.Generator] = None):
        self.traffic, self.template, self.stats = traffic, template, stats
        self.rng = rng
        self.items: list = []
        # a mix the general plan cannot express brings its own plan()
        own = os.path.join(BENCH, "traffic", traffic["name"] + ".py")
        self.plan = _module(own, "bench_traffic_" + traffic["name"].replace(
            ".", "_").replace("-", "_")).plan if os.path.exists(own) \
            else functools.partial(Q.plan, set_rng=set_rng)
        self._grow(ahead)

    def _grow(self, count: int) -> None:
        for p in self.plan(self.traffic, self.rng, count):
            q = Q.instantiate(self.template, self.stats, p.hardness)
            self.items.append((p, q, Q.to_program(q)))

    def get(self, i: int):
        if i >= len(self.items):
            self._grow(max(1024, i + 1 - len(self.items)))
        return self.items[i]


def _record(p, q, res, t0: float, t1: float) -> dict:
    rep = res.report
    return {"latency_s": t1 - t0, "end": t1, "status": rep.status,
            "hardness": p.hardness,
            "lp_pivots": rep.lp_pivots, "ilp_nodes": rep.ilp_nodes,
            "rungs": len(rep.fallbacks), "kind": p.kind,
            "cache_hits": rep.cache_hits, "cache_misses": rep.cache_misses,
            "answer": ref.Answer(q, rep.status, np.asarray(res.idx),
                                 np.asarray(res.mult), float(res.obj))}


def window(eng, streams: List[Stream], seconds: float,
           prof: Optional[Profiler]):
    """Closed loop: each client submits its next query when its last
    answer is back, until ``seconds`` have passed; the query in flight
    at the close is waited for and counted.  Returns the records and the
    window's length (start to the last answer)."""
    import jax.profiler
    recs: List[dict] = []
    lock = threading.Lock()
    errs: list = []

    def client(s: Stream, deadline: float):
        try:
            i = 0
            while time.monotonic() < deadline:
                p, q, pq = s.get(i)
                sess = eng.session(p.session_seed)
                t0 = time.monotonic()
                with jax.profiler.TraceAnnotation("bench.query"):
                    res = sess.solve(pq)
                t1 = time.monotonic()
                with lock:
                    recs.append(_record(p, q, res, t0, t1))
                i += 1
        except BaseException as e:   # handed to the caller below
            errs.append(e)

    if prof is not None:
        prof.start("window")
    t_start = time.monotonic()
    threads = [threading.Thread(target=client, name=f"bench-client-{c}",
                                args=(s, t_start + seconds))
               for c, s in enumerate(streams)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    if prof is not None:
        prof.stop()
    if errs:
        raise errs[0]
    t_end = max((r["end"] for r in recs), default=time.monotonic())
    return recs, t_end - t_start


def hierarchy_layers(hier) -> List[ref.Layer]:
    out = []
    for lyr in hier.layers[1:]:
        p, t = lyr.part, lyr.part.tree
        out.append(ref.Layer(p.gid, p.order, p.offsets, p.reps, p.boxes_lo,
                             p.boxes_hi, (t.attr, t.bound_off, t.bounds,
                                          t.children, t.root)))
    return out


def check_hierarchy(cfg: dict, X0, layers, key: int,
                    control: bool = False) -> Dict[str, tuple]:
    """The hierarchy's compared numbers, each with its limit.
    ``control`` puts the float32 recomputation of the representatives in
    the program's place."""
    given = ref.control_reps(X0, layers) if control else None
    h = ref.check_hierarchy(X0, layers, cfg["alpha"],
                            np.random.default_rng([key, 3]), PROBES,
                            given=given)
    return {"partition_faults": (h["partition_faults"], 0),
            "rep_rel_err": (h["rep_rel_err"], cfg["limits"]["rep_rel_err"])}


def check_answers(cfg: dict, cols, answers, key: int,
                  control: bool = False) -> Dict[str, tuple]:
    """The answers' compared numbers, each with its limit.  ``control``
    puts the float32 recomputation of the objectives in the program's
    place."""
    if control:
        answers = ref.control_answers(cols, answers)
    n = len(next(iter(cols.values())))
    lim = cfg["limits"]
    a = ref.check_answers(cols, answers, n)
    out = {"not_ok": (a["not_ok"], 0), "malformed": (a["malformed"], 0),
           "violation": (a["violation"], lim["violation"]),
           "obj_rel_err": (a["obj_rel_err"], lim["obj_rel_err"])}
    if "lp_gap" in lim:     # a configuration whose limit was measured
        out["lp_gap"] = (ref.lp_gap(cols, answers, n,
                                    np.random.default_rng([key, 4]),
                                    int(lim["lp_gap_sample"])),
                         lim["lp_gap"])
    return out


def seed_key(seed: int) -> int:
    """A non-negative key for numpy's generators, equal to the seed for
    every non-negative seed."""
    return seed % (1 << 64)


@dataclasses.dataclass
class Built:
    """Set-up up to the warm-up: the chip, the relation, the engine with
    its hierarchy."""
    devs: list
    clock: CompileClock
    spans: Spans
    prof: Optional[Profiler]
    cols: dict
    eng: object
    stats: dict


def build(cell: Cell, trace: bool, *, rows: Optional[int] = None,
          require_chip: bool = True, engine_cls=None,
          data_seed: Optional[int] = None,
          engine_seed: Optional[int] = None) -> Built:
    """The relation from the configuration's ``data_seed`` and the
    engine, partitioned; ``data_seed`` and ``engine_seed`` override the
    configuration's (for readings of other builds: ``bench/control.py``)."""
    jax = setup_jax()
    devs = jax.devices()
    dev = devs[0]
    on_chip = dev.platform == "tpu"
    if require_chip and (not on_chip or len(devs) < cell.chips):
        raise NoChip(f"{len(devs)} x {dev.platform} ({dev.device_kind}); "
                     f"the cell needs {cell.chips} TPU chip(s)")
    if on_chip:
        from bench.lib.peaks import peaks
        peaks(dev.device_kind)
    clock = CompileClock()
    spans = Spans()
    cfg, traffic = cell.config, cell.traffic
    if traffic.get("loop", "closed") != "closed":
        raise ValueError(f"loop {traffic['loop']!r}: only closed loops run")
    data_key = int(cfg["data_seed"] if data_seed is None else data_seed)
    eng_key = data_key if engine_seed is None else int(engine_seed)
    n = int(rows or cfg["rows"])

    with spans.span("relation"):
        cols = relation(cfg, n, data_key)
    prof = Profiler(os.path.join(OUT_DIR, "trace")) if trace else None
    if engine_cls is None:
        from repro.core.engine import PackageQueryEngine as engine_cls
    cache = None
    if cfg.get("cache_bytes"):
        from repro.core.qcache import QCache
        cache = QCache(int(cfg["cache_bytes"]))
    eng = engine_cls(cols, cfg["attrs"], d_f=cfg["d_f"], alpha=cfg["alpha"],
                     seed=eng_key, cache=cache)
    with spans.span("build"):
        built_in_thread(eng.partition, prof, clock, BUILD_TRACE_S)
    stats = Q.column_stats(cols, Q.template_attrs(traffic["template"]))
    return Built(devs, clock, spans, prof, cols, eng, stats)


def client_streams(cell: Cell, b: Built, key: int) -> List[Stream]:
    t = cell.traffic
    sk = int(t.get("set_seed", 0))
    return [Stream(t, t["template"], b.stats,
                   np.random.default_rng([key, 1, c]),
                   set_rng=np.random.default_rng([sk, 1, c]))
            for c in range(int(t["clients"]))]


def warm_up(cell: Cell, b: Built, key: int) -> None:
    """Queries of their own seed stream (and set), through the window's
    call."""
    t = cell.traffic
    warm = Stream(t, t["template"], b.stats, np.random.default_rng([key, 2]),
                  ahead=int(t["warmup_queries"]),
                  set_rng=np.random.default_rng([int(t.get("set_seed", 0)),
                                                 2]))
    with b.spans.span("warmup"):
        for p, _, pq in warm.items:
            b.eng.session(p.session_seed).solve(pq)


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: Optional[float] = None, rows: Optional[int] = None,
        require_chip: bool = True, control: bool = False,
        engine_cls=None) -> dict:
    """One run.  Returns the result line's fields plus ``checks`` and,
    with ``control``, ``control_checks``."""
    t_start = time.monotonic() if t_start is None else t_start
    b = build(cell, trace, rows=rows, require_chip=require_chip,
              engine_cls=engine_cls)
    key = seed_key(seed)
    warm_up(cell, b, key)
    gc.collect()
    gc.freeze()
    c_setup = b.clock.read()
    setup_s = time.monotonic() - t_start

    recs, window_s = window(b.eng, client_streams(cell, b, key), seconds,
                            b.prof)
    c_all = b.clock.read()
    mem = [d.memory_stats() or {} for d in b.devs]
    memory_peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)

    traced = []
    if b.prof is not None:
        from bench.lib import trace as tr
        for name, d, secs in b.prof.slices:
            s = tr.summarize(tr.find_xplane(d), f"bench.{name}")
            s.update(name=name, window_s=secs)
            traced.append(s)

    cfg, cols = cell.config, b.cols
    X0 = np.stack([cols[a] for a in cfg["attrs"]], axis=1)
    layers = hierarchy_layers(b.eng.hierarchy)
    answers = [r["answer"] for r in recs]
    b.eng = None
    gc.unfreeze()
    checks = {**check_answers(cfg, cols, answers, key),
              **check_hierarchy(cfg, X0, layers, key)}
    out = {"checks": checks}
    if control:
        out["control_checks"] = {
            **check_answers(cfg, cols, answers, key, control=True),
            **check_hierarchy(cfg, X0, layers, key, control=True)}

    dev = b.devs[0]
    record = {"queries": recs, "window_s": window_s, "setup_s": setup_s,
              "spans": b.spans.s,
              "compile": {"setup_s": c_setup[0], "setup_count": c_setup[1],
                          "window_s": c_all[0] - c_setup[0],
                          "window_count": c_all[1] - c_setup[1]},
              "trace": {s["name"]: s for s in traced} if trace else None,
              "platform": dev.platform,
              "layer_sizes": [len(X0)] + [len(l.reps) for l in layers]}
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.metrics[kind]:
        v = reader(m["name"])(record)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(b.devs), "memory_peak_bytes": memory_peak}
    if trace:
        # the window runs no device op on today's query path, so the
        # traced time is the build's slice and the whole window
        device["busy_s"] = sum(s["busy_s"] for s in traced)
        device["window_s"] = sum(s["window_s"] for s in traced)
        ops: Dict[str, float] = {}
        for s in traced:
            for name, secs in s["ops"]:
                ops[name] = ops.get(name, 0.0) + secs
        out["breakdown"] = {
            "device_ops": sorted(([k, v] for k, v in ops.items()),
                                 key=lambda kv: -kv[1])[:10],
            "idle_gaps": sorted((g for s in traced for g in s["gaps"]),
                                key=lambda g: -g[1])[:10]}
    out.update(
        correct=all(v <= lim for v, lim in checks.values()),
        attempted=len(recs),
        failed=sum(r["status"] != "ok" for r in recs),
        metrics=metrics, device=device, record=record)
    return out


def result_line(out: dict) -> str:
    """The contract's last line: the compared numbers come last."""
    line = {k: out[k] for k in ("correct", "attempted", "failed", "metrics",
                                "device")}
    if "breakdown" in out:
        line["breakdown"] = out["breakdown"]
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in out["checks"].items()}
    return json.dumps(line)


def check_lines(checks: Dict[str, tuple]) -> List[str]:
    return [f"check {k}: {v!r} (limit {lim!r})"
            for k, (v, lim) in checks.items()]


def summary(out: dict) -> str:
    rec = out["record"]
    lat = sorted(r["latency_s"] for r in rec["queries"])
    return (f"[bench] layers {rec['layer_sizes']}, setup {rec['setup_s']:.3f}s "
            f"(build {rec['spans'].get('build', 0.0):.3f}s, compile "
            f"{rec['compile']['setup_s']:.3f}s over "
            f"{rec['compile']['setup_count']} executables), window "
            f"{rec['window_s']:.3f}s, {len(lat)} queries, "
            f"{out['failed']} not ok, {rec['compile']['window_count']} "
            f"compiles in the window, latency min/max "
            f"{(lat or [0])[0]:.4f}/{(lat or [0])[-1]:.4f}s")


def main(args, t_start: float) -> int:
    cell = load_cell(args.workload)
    rehearsal = args.rows is not None
    try:
        out = run(cell, args.seed, args.seconds, bool(args.trace),
                  t_start=t_start, rows=args.rows,
                  require_chip=not rehearsal)
    except NoChip as e:
        print(f"bench: no chip for this cell: {e}", file=sys.stderr)
        return 1
    print(summary(out), file=sys.stderr)
    if rehearsal:
        print(f"bench: every step ran, at {args.rows} rows on "
              f"{out['device']['platform']}: a rehearsal has no result",
              file=sys.stderr)
    for line in check_lines(out["checks"]):
        print(line, file=sys.stderr)
    if rehearsal:
        return 1
    sys.stderr.flush()
    print(result_line(out), flush=True)
    return 0
