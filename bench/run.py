#!/usr/bin/env python3
"""The chip benchmark of the served graph-query path: one cell, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``bench/configs/<config>.json``, the data set) under a traffic mix
(``bench/traffic/<traffic>.json``).  One process, in this order:

1. checks that JAX's devices are TPUs, as many as the cell asks for (no
   CPU fallback: it exits 3 and prints no result otherwise);
2. generates the data set from ``--seed`` and builds the program's store;
3. builds ``GOpt(store, backend="jax")`` (statistics and GLogue);
4. prepares the mix's queries and draws the window's requests;
5. warms up: every plan at every number of distinct bindings a wave of its
   traffic can hold, with bindings drawn like the window's but none that
   the window sends;
6. drives the window through ``GOpt.serve`` (the server's own defaults):
   ``QueryServer.submit`` at the scheduled times on a generator thread
   (open loop) or by each client on its last answer (closed loop),
   ``step`` on this thread, each finished table pulled to the host;
7. checks every served table against the plain reference
   (``bench/reference.py``), after the program's state is freed;
8. prints the result as its last line of standard output.

With ``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` the window is traced by the JAX profiler and the result
holds the cell's per-layer metrics, each read by its own reader
(``bench/metrics/<name>.py``), and the trace's ``breakdown``.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import shutil
import sys
import threading
import time
from pathlib import Path

T_START = time.perf_counter()
HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:0] = [str(ROOT / "src"), str(HERE)]

import compare  # noqa: E402
import datagen  # noqa: E402
import reference  # noqa: E402
import stats  # noqa: E402
import traffic  # noqa: E402

# engine fallbacks a healthy request may record: a fused chain whose
# capacity schedule grew re-runs that execution on the per-hop loop
ALLOWED_FALLBACKS = {"chain_capacity"}
# most warm-up passes over the wave sizes (they stop at one that compiles
# nothing: a shape's second run can still compile grown capacities)
WARM_PASSES = 3
# requests drawn for a closed loop's window (far more than one serves)
CLOSED_STREAM = 1 << 16
# a run waits this long past the window's close for its last answers
GRACE_S = 60.0
# the compile cache stays inside the checkout, at a fixed path
CACHE_DIR = ".jax_cache"
TRACE_DIR = ".bench_trace"


class BenchError(Exception):
    """The cell cannot run here (no chip, a missing file, a bad name)."""


# --------------------------------------------------------------- the cell
def load_cell(root: Path, workload: str) -> dict:
    """Everything the cell's files say, found by the names in
    ``BENCHMARK.json``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[cell["config"]]
    cfg = json.loads((root / cfg_entry["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic"
                      / f"{cell['traffic']}.json").read_text())

    def mine(metric):
        return workload in metric.get("workloads", [workload])
    return {"name": workload, "cell": cell, "config": cfg, "mix": mix,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)],
            "root": root}


def load_reader(root: Path, name: str):
    """The per-layer metric ``name``'s reader, ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------- the device
def require_tpu(chips: int) -> dict:
    """JAX's devices must be TPUs, at least ``chips`` of them."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise BenchError(f"no TPU: JAX's first device is "
                         f"{info['platform']} ({info['kind']})")
    if info["count"] < chips:
        raise BenchError(f"the cell needs {chips} TPU chips; JAX sees "
                         f"{info['count']}")
    return info


def require_program():
    """The system under test must be in the checkout beside the benchmark."""
    if importlib.util.find_spec("repro") is None:
        raise BenchError(f"the program (src/repro) is not in {ROOT}")


def require_compiled(ops):
    """The jax operator set must run its Pallas kernels compiled."""
    if ops._interpret:
        raise BenchError("the jax operator set would run its Pallas kernels "
                         "in interpret mode")


def configure_jax(root: Path):
    """The persistent compile cache inside the checkout, for every
    program however small (the default keeps only compiles over 1 s)."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCount:
    """XLA compiles (and persistent-cache loads) as JAX reports them."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0
        self.names: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, _secs, fun_name="?", **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.names[fun_name] = self.names.get(fun_name, 0) + 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self) -> tuple:
        return self.compiles, self.cache_hits

    def names_since(self, before: dict) -> dict:
        """Compiles per function name since the copy ``before``."""
        return {k: n - before.get(k, 0) for k, n in self.names.items()
                if n > before.get(k, 0)}


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


# ---------------------------------------------------------------- set-up
def window_requests(mix: dict, v_count: dict, seed: int, seconds: float,
                    warm: list) -> list:
    """The requests the window will send: an open loop's schedule, or the
    stream a closed loop's clients take their next request from; none
    with a binding of ``warm`` (the warm-up's, per query)."""
    used = [{traffic.binding_key(p) for p in ps} for ps in warm]
    if mix["arrivals"]["process"] == "closed":
        return traffic.closed_stream(mix, v_count, seed, CLOSED_STREAM, used)
    return traffic.schedule(mix, v_count, seed, seconds, used)


def set_up(run: dict, seed: int, seconds: float, log,
           counter=None) -> dict:
    """Data, store, GOpt, prepared plans, the window's requests, a warm
    server."""
    from repro.core.gopt import GOpt
    cfg, mix = run["config"], run["mix"]
    phases = {}
    t = time.perf_counter()
    data = datagen.generate(cfg, seed)
    store = datagen.build_program_store(data)
    phases["store_s"] = time.perf_counter() - t
    log(f"store: {cfg['name']} sf={cfg['sf']} seed={seed} "
        f"{store.n_vertices} vertices {store.n_edges} edges "
        f"({phases['store_s']:.3f} s, host)")
    t = time.perf_counter()
    gopt = GOpt(store, backend="jax")
    phases["gopt_s"] = time.perf_counter() - t
    log(f"GOpt (statistics, GLogue): {phases['gopt_s']:.3f} s (host)")
    require_compiled(gopt.spec.operators(store))
    t = time.perf_counter()
    srv = gopt.serve(backend="jax")
    plans = [gopt.prepare(q["text"], backend="jax") for q in mix["queries"]]
    sizes = range(1, largest_wave(mix, srv.max_wave) + 1)
    warm = traffic.warmup_bindings(mix, store.v_count, seed,
                                   WARM_PASSES * len(sizes))
    window = window_requests(mix, store.v_count, seed, seconds, warm)
    phases["prepare_s"] = time.perf_counter() - t
    t = time.perf_counter()
    warm_waves = warm_up(srv, plans, sizes, warm, seed, log, counter)
    phases["warmup_s"] = time.perf_counter() - t
    log("set-up: " + " ".join(f"{k}={v:.3f}" for k, v in phases.items())
        + f" (host clock; warm-up {warm_waves} waves)")
    return {"data": data, "store": store, "gopt": gopt, "srv": srv,
            "plans": plans, "phases": phases, "window": window,
            "warm": warm}


def server_max_wave() -> int:
    """The largest wave ``GOpt.serve`` forms by default."""
    import inspect
    from repro.graphdb.serve import QueryServer
    return inspect.signature(QueryServer).parameters["max_wave"].default


def largest_wave(mix: dict, max_wave: int) -> int:
    """The most distinct bindings one wave of the mix can hold: a closed
    loop has at most one request per client pending."""
    arrivals = mix["arrivals"]
    if arrivals["process"] == "closed":
        return min(arrivals["clients"], max_wave)
    return max_wave


def warm_up(srv, plans: list, sizes, binds: list, seed: int, log=None,
            counter=None) -> int:
    """Serve what the window will serve before it opens: every plan in a
    wave of every size in ``sizes`` (the program keys some shapes by the
    wave's exact number of distinct bindings), with bindings from
    ``binds`` (per plan, ``len(sizes)`` for each pass; the window never
    sends them).  The waves of a pass come in an order drawn from the
    seed, plans interleaved as in the window; passes repeat until one
    compiles nothing (a shape's second run can still compile grown
    capacities), at most ``WARM_PASSES``."""
    per_pass = len(sizes)
    rng = traffic.rng_for(seed, traffic.WARM_ORDER)
    counter = counter or CompileCount()
    waves = 0
    for n in range(WARM_PASSES):
        p0 = counter.mark()
        t_pass = time.perf_counter()
        one = []
        for qi, mine in enumerate(binds):
            ours = mine[n * per_pass:(n + 1) * per_pass]
            for k in sizes:
                one.append((qi, ours[:k]))
        for i in rng.permutation(len(one)):
            qi, wave = one[i]
            for params in wave:
                srv.submit(plans[qi], params)
            for r in srv.drain():
                if r.status != "done":
                    raise BenchError(f"warm-up request ended "
                                     f"{r.status}: {r.error}")
            waves += 1
        p1 = counter.mark()
        if log is not None:
            log(f"warm-up pass {n + 1}: waves of 1..{len(sizes)} per plan, "
                f"{time.perf_counter() - t_pass:.3f} s, {p1[0] - p0[0]} XLA "
                f"compiles ({p1[1] - p0[1]} from the cache)")
        if p1[0] == p0[0]:
            break
    return waves


# ---------------------------------------------------------------- window
class Record:
    """One request of the window, on the window's clock (seconds from its
    start)."""
    __slots__ = ("query", "params", "due_s", "sent_s", "host_s",
                 "gave_up_s", "req", "cols", "nrows", "status",
                 "queue_delay_s", "fallbacks")

    def __init__(self, query: int, params: dict, due_s: float):
        self.query, self.params, self.due_s = query, params, due_s
        self.sent_s = self.host_s = None
        self.gave_up_s = None
        self.req = self.cols = self.nrows = None
        self.status = "never admitted"
        self.queue_delay_s = None
        self.fallbacks = set()

    def settle(self):
        """Keep what the metrics read of the server's request, and let the
        request (and through it the program's state) go."""
        r, self.req = self.req, None
        if r is not None:
            self.status = r.status
            self.queue_delay_s = r.queue_delay_s
            self.fallbacks = set((r.stats.fallbacks if r.stats else {})
                                 or {})


def drive_window(srv, plans: list, mix: dict, window: list,
                 seconds: float, on_open=None) -> dict:
    """Serve one window of the mix; ``window`` is ``window_requests``'s
    list.  Returns the records and the window's wave marks.  ``on_open``
    runs just before the window's clock starts."""
    from repro.core.errors import ParamError
    from repro.graphdb.serve import ServeOverload, ServeQuarantined
    arrivals = mix["arrivals"]
    closed = arrivals["process"] == "closed"
    records: list[Record] = []
    lock = threading.Lock()
    arrived = threading.Event()
    marks = {"waves": len(srv.stats.exec_s),
             "breaker_trips": srv.stats.breaker_trips}
    if on_open is not None:
        on_open()
    t0 = time.perf_counter() + 0.05
    t_end = t0 + seconds

    def send(rec: Record):
        with _span("bench.submit"):
            due = t0 + rec.due_s
            try:
                rec.req = srv.submit(plans[rec.query], rec.params,
                                     arrival_s=due)
            except (ServeOverload, ServeQuarantined, ParamError) as exc:
                rec.status = f"rejected ({type(exc).__name__})"
            rec.sent_s = time.perf_counter() - t0
        with lock:
            records.append(rec)
        arrived.set()

    gen = None
    if closed:
        stream = iter(window)
    else:
        plan = [Record(r.query, r.params, r.due_s) for r in window]

        def generate():
            for rec in plan:
                wait = t0 + rec.due_s - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                send(rec)
        gen = threading.Thread(target=generate, name="bench-generator",
                               daemon=True)
    span = _span("bench.window")
    while time.perf_counter() < t0:
        time.sleep(0.001)
    span.__enter__()
    window_open = True
    if gen is not None:
        gen.start()
    else:
        for _ in range(arrivals["clients"]):
            r = next(stream)
            send(Record(r.query, r.params, time.perf_counter() - t0))
    by_req = {}
    seen = 0
    deadline = t_end + GRACE_S
    while True:
        now = time.perf_counter()
        if window_open and now >= t_end:
            span.__exit__(None, None, None)
            window_open = False
        with lock:
            for rec in records[seen:]:
                if rec.req is not None:
                    by_req[id(rec.req)] = rec
            seen = len(records)
        if srv.pending:
            with _span("bench.step"):
                done = srv.step()
        else:
            # nothing queued: join the wave still running, if any
            with _span("bench.flush"):
                done = srv.flush()
        if done:
            with _span("bench.deliver"):
                for r in done:
                    rec = by_req.get(id(r))
                    if rec is None:
                        continue
                    if r.status == "done":
                        rec.cols = {k: _to_host(v)
                                    for k, v in r.table.cols.items()}
                        rec.nrows = int(r.table.nrows)
                        rec.host_s = time.perf_counter() - t0
                    # a closed-loop client sends its next request on any
                    # answer, while the window is open
                    if closed and time.perf_counter() < t_end:
                        nxt = next(stream)
                        send(Record(nxt.query, nxt.params,
                                    time.perf_counter() - t0))
            continue
        if srv.pending:
            continue
        settled = (gen is None or not gen.is_alive()) and now >= t_end \
            and all(rec.req is None or rec.req.status != "pending"
                    for rec in records)
        if settled or now >= deadline:
            break
        with _span("bench.idle"):
            arrived.wait(0.002)
            arrived.clear()
    if window_open:
        span.__exit__(None, None, None)
    if gen is not None:
        gen.join(timeout=GRACE_S)
    gave_up = time.perf_counter() - t0
    for rec in records:
        if rec.host_s is None:
            rec.gave_up_s = gave_up
        rec.settle()
    marks["ladder_steps"] = srv.stats.breaker_trips - marks["breaker_trips"]
    return {"records": records, "marks": marks, "sent": len(records),
            "window_s": seconds}


def _to_host(v):
    import numpy as np
    return np.asarray(v)


def failed_reasons(rec: Record, ladder_steps: int = 0) -> list:
    """Why a window request counts as failed (empty: it did not).  A window
    in which the server stepped any plan down its degradation ladder
    (``ServeStats.breaker_trips``) counts every request as possibly run
    degraded."""
    out = []
    if rec.status != "done" or rec.host_s is None:
        out.append(f"ended {rec.status}")
    if ladder_steps:
        out.append("the server stepped down its ladder in the window")
    bad = rec.fallbacks - ALLOWED_FALLBACKS
    if bad:
        out.append(f"fell back ({sorted(bad)})")
    return out


# ----------------------------------------------------------------- check
def check(data: dict, mix: dict, records: list) -> dict:
    """Every served table of the window against the reference."""
    g = reference.Graph(data)
    refs = {q["reference"]: reference.load(q["reference"])
            for q in mix["queries"]}
    wrong, missing, first = 0, 0, None
    answers = {}
    for rec in records:
        if rec.cols is None:
            missing += 1
            continue
        q = mix["queries"][rec.query]
        key = (rec.query, json.dumps(rec.params, sort_keys=True))
        if key not in answers:
            answers[key] = refs[q["reference"]](g, rec.params)
        why = compare.check_table(rec.cols, rec.nrows, answers[key],
                                  q["result"])
        if why is not None:
            wrong += 1
            first = first or f"{q['name']}{rec.params}: {why}"
    return {"tables_wrong": wrong, "answers_missing": missing,
            "compared": len(records) - missing, "first_wrong": first}


# ----------------------------------------------------------------- a run
def run_cell(run: dict, seed: int, seconds: float, trace: bool,
             device: dict, log) -> dict:
    """Set up, drive one window, check it; returns the result object."""
    counter = CompileCount()
    st = set_up(run, seed, seconds, log, counter)
    return measure(run, st, seconds, trace, device, log, counter)


def measure(run: dict, st: dict, seconds: float, trace: bool,
            device: dict, log, counter: CompileCount) -> dict:
    """Drive one window through the set-up server ``st["srv"]``, free the
    program's state, check every table; returns the result object."""
    root = run["root"]
    srv, mix = st["srv"], run["mix"]
    trace_dir = root / TRACE_DIR / run["name"]

    def open_trace():
        if trace:
            import jax
            shutil.rmtree(trace_dir, ignore_errors=True)
            # the device's ops and the benchmark's spans; no Python
            # function events, which would cost the host and the disk
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)

    c0 = counter.mark()
    names0 = dict(counter.names)
    t_open = time.perf_counter()
    out = drive_window(srv, st["plans"], mix, st["window"], seconds,
                       on_open=open_trace)
    setup_s = t_open - T_START
    c1 = counter.mark()
    if trace:
        import jax
        jax.profiler.stop_trace()
    records = out["records"]
    s = srv.stats
    w = out["marks"]["waves"]
    window_waves = {"exec_s": s.exec_s[w:], "sizes": s.wave_sizes[w:],
                    "kernel_compiles": sum(s.wave_compiles[w:])}
    log(f"window: {len(records)} requests sent, "
        f"{sum(r.host_s is not None for r in records)} answered, "
        f"{len(window_waves['exec_s'])} waves; compiles in the window: "
        f"{c1[0] - c0[0]} XLA ({c1[1] - c0[1]} from the persistent cache), "
        f"{window_waves['kernel_compiles']} program-counted")
    if c1[0] > c0[0]:
        log(f"compiled in the window: {counter.names_since(names0)}")
    fail = {}
    steps = out["marks"]["ladder_steps"]
    for rec in records:
        for why in failed_reasons(rec, steps):
            fail[why] = fail.get(why, 0) + 1
    n_failed = sum(1 for rec in records if failed_reasons(rec, steps))
    if fail:
        log(f"failed requests: {n_failed} ({fail})")
    import jax
    mem = jax.devices()[0].memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    # free the program's state before the reference runs
    data = st["data"]
    srv.close()
    phases = st["phases"]
    st.clear()
    del srv
    gc.collect()
    t = time.perf_counter()
    verdict = check(data, mix, records)
    log(f"check: {verdict['compared']} tables compared in "
        f"{time.perf_counter() - t:.3f} s (host)"
        + (f"; first wrong: {verdict['first_wrong']}"
           if verdict["first_wrong"] else ""))
    ctx = {"records": records, "waves": window_waves, "phases": phases,
           "window_s": out["window_s"], "setup_s": setup_s}
    result = {"correct": verdict["tables_wrong"] == 0
              and verdict["answers_missing"] == 0,
              "attempted": len(records), "failed": n_failed,
              "metrics": {}, "device": dict(device,
                                            memory_peak_bytes=peak)}
    if trace:
        import trace_reduce
        t = time.perf_counter()
        red = trace_reduce.reduce(trace_reduce.find_xplane(str(trace_dir)))
        log(f"trace: {red['n_ops']} device ops on {red['devices']} "
            f"device(s), busy {red['busy_s']:.6f} s of "
            f"{red['window_s']:.6f} s, read in "
            f"{time.perf_counter() - t:.3f} s")
        ctx["trace"] = red
        result["device"]["busy_s"] = red["busy_s"]
        result["device"]["window_s"] = red["window_s"]
        for m in run["per_layer"]:
            v = load_reader(root, m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    else:
        e2e = stats.end_to_end(records, out["window_s"])
        e2e["setup_s"] = setup_s
        for m in run["end_to_end"]:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
    result["checks"] = {
        "tables_wrong": {"value": verdict["tables_wrong"], "limit": 0},
        "answers_missing": {"value": verdict["answers_missing"],
                            "limit": 0}}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        run = load_cell(ROOT, args.workload)
        require_program()
        configure_jax(ROOT)
        device = require_tpu(run["cell"]["chips"])
    except (BenchError, OSError, KeyError, ValueError) as exc:
        print(f"bench FAILED: {exc}", file=sys.stderr, flush=True)
        return 3
    tag = f"[{device['platform']} {device['kind']} x{device['count']}]"

    def log(msg):
        print(f"bench {args.workload} {tag} {msg}", flush=True)

    try:
        result = run_cell(run, args.seed, args.seconds, bool(args.trace),
                          device, log)
    except BenchError as exc:
        print(f"bench FAILED: {exc}", file=sys.stderr, flush=True)
        return 1
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
