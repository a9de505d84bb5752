"""Run one cell of the benchmark once.

    python3 perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic come from ``BENCHMARK.json``
at the root of the checkout; each is found by name: the configuration
in ``perf/configs/<config>.json``, the traffic in
``perf/traffic/<traffic>.json``, and the configuration's ``generator``,
``entry`` and ``reference`` in ``perf/generators/<generator>.py``,
``perf/entries/<entry>.py`` and ``perf/reference/<reference>.py``; each
end-to-end metric in ``perf/end_to_end/<metric>.py`` and each per-layer
metric in ``perf/metrics/<metric>.py``.  Nothing here knows a data
model: a configuration of another one comes as new files of those
kinds.

The generator makes the cell's traffic pool, a list of requests each a
list of items, with ``make_pool(cfg, traffic, seed)``, and gives a
request's size with ``request_size(request, traffic) -> (client ops,
events)``.  The entry turns a request into the program's input with
``prepare(cfg, items)``, runs it with ``check(prepared)``, and reads
the result with ``rows(result, n_items) -> ([{label: verdict}] per
item, [(engine, kernel)] per device row)``.  The reference gives an
item's labels with the verdicts the generator knows, ``known(cfg,
traffic, item) -> {label: verdict or None}``, and decides labels by its
own search, ``search(cfg, traffic, item, labels) -> {label: verdict}``
(``perf/verify.py``).  A metric named ``<base>.<variant>`` with no
reader file of its own is read by ``<base>.py``: a new cell takes an
existing per-layer quantity as a new ``BENCHMARK.json`` entry that
lists it.

A run makes the cell's traffic pool from ``--seed``, warms up by
checking every request of the pool once and then more until one
compiles nothing, and then, for ``--seconds``, checks requests one at
a time in a fixed order (a closed loop with one client).  After the
window it reads the device's peak memory, compares every verdict with
the reference (``perf/verify.py``), and prints one JSON line.  With
``--trace 1`` the program's ``obs`` spans and counters are on for the
window and a ``jax.profiler`` trace covers its first requests; the
line then carries the per-layer metrics.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

PERF = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PERF)
# the program under test sits at the checkout's root; the metric
# readers share helpers in perf/metrics.  Both go after the standard
# library and site-packages, so that a file added there by name never
# shadows a module that jax or the program imports.
sys.path += [ROOT, os.path.join(PERF, "metrics")]
#: JAX's persistent compile cache: a fixed path inside the checkout
CACHE_DIR = os.path.join(PERF, ".cache", "jax")
TRACE_DIR = os.path.join(PERF, ".cache", "trace")


class RunError(Exception):
    """The run cannot produce a result; exits nonzero, prints none."""


def load_module(*parts):
    path = os.path.join(PERF, *parts)
    if not os.path.isfile(path):
        raise RunError(f"missing {os.path.relpath(path, ROOT)}")
    name = "perf_" + "_".join(parts).replace(".py", "").replace("/", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts):
    path = os.path.join(PERF, *parts)
    if not os.path.isfile(path):
        raise RunError(f"missing {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


#: where the modules a configuration names live, by its key
MODULE_DIRS = {"generator": "generators", "entry": "entries",
               "reference": "reference"}


def reader(kind_dir, name):
    """The reader module of metric ``name``: ``<name>.py``, or for a
    ``<base>.<variant>`` with no file of its own, ``<base>.py``."""
    own = name + ".py"
    if "." in name and not os.path.isfile(os.path.join(PERF, kind_dir,
                                                        own)):
        own = name.split(".", 1)[0] + ".py"
    return load_module(kind_dir, own)


class Cell:
    """One ``workloads`` entry and everything it names; ``shrink``
    ({"config": {...}, "traffic": {...}}) is merged over its files, as
    the tests cut a cell to a test run's size."""

    def __init__(self, bench, name, shrink=None):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise RunError(f"no workload {name!r} in BENCHMARK.json")
        w = cells[name]
        self.name = name
        self.chips = w["chips"]
        self.config = load_json("configs", w["config"] + ".json")
        for key in MODULE_DIRS:
            if key not in self.config:
                raise RunError(f"configuration {w['config']!r} names no "
                               f"{key}")
        self.traffic = load_json("traffic", w["traffic"] + ".json")
        if shrink:
            self.config.update(shrink.get("config", {}))
            self.traffic.update(shrink.get("traffic", {}))

        def mine(m):
            return name in m.get("workloads", [name])

        self.end_to_end = [m for m in bench["end_to_end"] if mine(m)]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if m["moves"] in reported and mine(m)]

    def module(self, key):
        """The configuration's ``generator``, ``entry`` or
        ``reference``."""
        return load_module(MODULE_DIRS[key], self.config[key] + ".py")


def hygiene():
    """Refuse a run whose program would not be the one as committed."""
    env = sorted(k for k in os.environ if k.startswith("JEPSEN_TPU_"))
    if env:
        raise RunError(f"JEPSEN_TPU_* set in the environment: {env}")
    if os.path.exists(os.path.join(ROOT, "calibration.json")):
        raise RunError("a calibration.json in the checkout would retune "
                       "the engine")


class CompileCounter:
    """Backend compiles and persistent-cache hits, from jax's own
    monitoring events (as ``chip_smoke._CompileCounter``)."""

    def __init__(self, jax):
        self.compiles = self.hits = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


def start_jax(chips, allow_cpu):
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    # libtpu's own logs go to /tmp/tpu_logs unless told otherwise
    if "TPU_LOG_DIR" not in os.environ:
        os.environ["TPU_LOG_DIR"] = os.path.join(PERF, ".cache", "tpu_logs")
        os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not allow_cpu:
        raise RunError(f"no accelerator: jax.devices() is {platform}")
    if len(devices) != chips and not allow_cpu:
        raise RunError(f"the cell asks for {chips} chips, jax sees "
                       f"{len(devices)}")
    return jax, devices


def engine_knobs():
    """The engine's resolved settings, for an earlier line."""
    from jepsen_tpu.engine import execution, planning
    from jepsen_tpu.ops import cycles, dense
    from jepsen_tpu.parallel import mesh

    m = mesh.engine_default_mesh()
    return {"window": execution.default_window(),
            "flush_rows": planning.flush_rows_default(),
            "row_bucket_floor": execution.row_bucket_floor(),
            "closure_impl": cycles.closure_impl(),
            "dense_union": dense._union_mode(),
            "mesh_devices": 1 if m is None else int(m.devices.size)}


def peak_bytes(devices):
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and stats.get("peak_bytes_in_use") is not None:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def usage(gc_total):
    """Counters whose change over a request tells a slow request's
    cause apart: this thread's CPU, the process's CPU, involuntary
    context switches, page faults, GC pauses, and the whole machine's
    busy and stolen CPU seconds (``/proc/stat``)."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    busy = steal = 0.0
    try:
        with open("/proc/stat") as f:
            cpu = [int(x) for x in f.readline().split()[1:]]
        tick = os.sysconf("SC_CLK_TCK")
        # user nice system idle iowait irq softirq steal
        busy = (cpu[0] + cpu[1] + cpu[2] + cpu[5] + cpu[6]) / tick
        steal = (cpu[7] if len(cpu) > 7 else 0) / tick
    except (OSError, ValueError, IndexError):
        pass
    return (time.thread_time(), ru.ru_utime + ru.ru_stime, ru.ru_nivcsw,
            ru.ru_minflt, ru.ru_majflt, gc_total[0], busy, steal)


USAGE_KEYS = ("thread_cpu_s", "process_cpu_s", "invol_switches",
              "minor_faults", "major_faults", "gc_s", "machine_busy_s",
              "machine_steal_s")


def slow_requests(requests, factor=1.25):
    """The window's requests slower than ``factor`` times the median
    of their own pool entry, each with its counters, beside the
    median request's: what a stalled request spent its time on."""
    by_idx = {}
    for r in requests:
        if r.error is None and r.usage is not None:
            by_idx.setdefault(r.idx, []).append(r)
    slow, typical = [], []
    for reqs in by_idx.values():
        med = statistics.median(r.t1 - r.t0 for r in reqs)
        for r in reqs:
            row = dict(zip(USAGE_KEYS, r.usage), wall_s=r.t1 - r.t0)
            typical.append(row)
            if r.t1 - r.t0 > factor * med:
                slow.append(dict(row, idx=r.idx, median_wall_s=med))
    if not typical:
        return None
    keys = ("wall_s",) + USAGE_KEYS
    return {"slow": slow[:5], "n_slow": len(slow),
            "median": {k: statistics.median(t[k] for t in typical)
                       for k in keys}}


class Request:
    """One timed request and what it returned."""

    __slots__ = ("idx", "t0", "t1", "mono0", "ops", "events", "rows",
                 "engines", "answers", "error", "usage")

    def __init__(self, idx, ops, events):
        self.idx = idx
        self.ops = ops
        self.events = events
        self.t0 = self.t1 = self.mono0 = None
        self.rows = 0
        self.engines = []
        self.answers = []
        self.error = None
        self.usage = None


class Run:
    """What a run recorded, for the metric readers."""

    def __init__(self):
        self.setup_s = None
        self.window_s = None
        self.requests = []
        self.window_compiles = None
        self.spans = []
        self.spans_dropped = None
        self.obs_metrics = []
        self.profile = None
        self.peaks = None


def window_order(n):
    """The window's request order: every request once per cycle, in
    bit-reversed order, so that any prefix spreads over the pool (whose
    requests are sorted by size) as evenly as a whole cycle.  The seed
    changes what the requests hold, not their sizes or order."""
    bits = max(1, (n - 1).bit_length())
    return [i for i in (int(format(j, f"0{bits}b")[::-1], 2)
                        for j in range(1 << bits)) if i < n]


def run_cell(argv=None, allow_cpu=False, wrap_check=None, shrink=None):
    """One run; returns (result dict, checks).  The tests pass
    ``allow_cpu``, ``shrink`` ({"config": {...}, "traffic": {...}}
    merged over the cell's files, to fit a test run) and ``wrap_check``
    (to break the timed path underneath)."""
    ap = argparse.ArgumentParser(description="run one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    hygiene()
    if importlib.util.find_spec("jepsen_tpu") is None:
        raise RunError("the program (jepsen_tpu/) is not in the checkout")
    bench_path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.isfile(bench_path):
        raise RunError("no BENCHMARK.json at the checkout's root")
    with open(bench_path) as f:
        bench = json.load(f)
    cell = Cell(bench, args.workload, shrink)
    run = Run()
    gen = cell.module("generator")
    entry = cell.module("entry")
    ref = cell.module("reference")
    verify = load_module("verify.py")
    peaks_table = load_json("peaks.json")

    jax, devices = start_jax(cell.chips, allow_cpu)
    kind = devices[0].device_kind
    if devices[0].platform == "tpu":
        if kind not in peaks_table["devices"]:
            raise RunError(f"no peaks for device kind {kind!r} in "
                           "perf/peaks.json")
        run.peaks = peaks_table["devices"][kind]
    counter = CompileCounter(jax)
    from jepsen_tpu import obs

    obs.disable()
    print(json.dumps({"engine": engine_knobs(), "cache_dir": CACHE_DIR}),
          flush=True)

    t0 = time.perf_counter()
    pool = gen.make_pool(cell.config, cell.traffic, args.seed)
    gen_s = time.perf_counter() - t0
    items = {}
    prepared = []
    requests_items = []
    for r, req in enumerate(pool):
        ids = []
        for j, item in enumerate(req):
            items[(r, j)] = item
            ids.append((r, j))
        requests_items.append(ids)
        prepared.append(entry.prepare(cell.config, req))
    check = entry.check if wrap_check is None else wrap_check(entry.check)
    gc_total = [0.0]

    def one(r, record):
        req = Request(r, *gen.request_size(pool[r], cell.traffic))
        u0 = usage(gc_total)
        req.mono0 = time.monotonic_ns()
        req.t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("perf/request", idx=r):
                result = check(prepared[r])
            req.t1 = time.perf_counter()
            req.usage = tuple(b - a for a, b in zip(u0, usage(gc_total)))
            verdicts, req.engines = entry.rows(result, len(pool[r]))
            req.rows = len(req.engines)
            req.answers = list(zip(requests_items[r], verdicts))
        except Exception as e:  # noqa: BLE001 — a failed request counts
            req.t1 = time.perf_counter()
            req.error = repr(e)[:300]
            import traceback

            traceback.print_exc()
        if record:
            run.requests.append(req)
        return req

    # -- warm-up: every request once, then until one compiles nothing
    n_req = len(pool)
    warm = 0
    for r in range(n_req) if n_req > 1 else (0, 0):
        c0 = counter.compiles
        one(r, False)
        warm += 1
    for r in window_order(n_req)[:3]:
        if counter.compiles == c0:
            break
        c0 = counter.compiles
        one(r, False)
        warm += 1
    print(json.dumps({"setup": {"generate_s": gen_s, "warm_requests": warm,
                                "compiles": counter.compiles,
                                "cache_hits": counter.hits,
                                "last_warm_compiled": counter.compiles - c0}}),
          flush=True)

    # -- the window
    order = window_order(n_req)
    trace_n = cell.traffic.get("trace_requests", 1) if args.trace else 0
    if args.trace:
        obs.enable(reset=True)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    # the pool and what warm-up left live for the whole run: keep the
    # collector from walking their millions of objects at some random
    # point of the window
    gc.collect()
    gc.freeze()
    c0 = counter.compiles
    gc_pauses = []
    gc_t0 = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            gc_t0[0] = time.perf_counter()
        else:
            gc_pauses.append((info["generation"],
                              time.perf_counter() - gc_t0[0]))
            gc_total[0] += gc_pauses[-1][1]

    gc.callbacks.append(on_gc)
    run.setup_s = time.perf_counter() - T_START
    t_w0 = time.perf_counter()
    k = 0
    tracing = False
    while time.perf_counter() - t_w0 < args.seconds:
        if k == 0 and trace_n:
            # host annotations and device ops only: the Python tracer
            # (on by default) slows the host fivefold and writes
            # hundreds of MB a request
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
            tracing = True
        one(order[k % len(order)], True)
        k += 1
        if tracing and k == trace_n:
            jax.profiler.stop_trace()
            tracing = False
    if tracing:
        jax.profiler.stop_trace()
    run.window_s = time.perf_counter() - t_w0
    gc.callbacks.remove(on_gc)
    run.window_compiles = counter.compiles - c0
    if args.trace:
        run.spans = [s.to_dict() for s in obs.tracer().finished()]
        run.spans_dropped = obs.tracer().dropped
        run.obs_metrics = obs.registry().snapshot()
        obs.disable()
    peak = peak_bytes(devices)

    # -- the trace, then the comparison with the reference
    trace_mod = load_module("xplane.py")
    if args.trace:
        run.profile = trace_mod.load(TRACE_DIR)
    expected = verify.Expected(ref, cell.config, cell.traffic)
    answered = [a for req in run.requests for a in req.answers]
    for req in run.requests:
        for item_id in requests_items[req.idx][len(req.answers):]:
            answered.append((item_id, {}))   # never answered
    audit = verify.audit_sample(args.seed, [a[0] for a in answered],
                                cell.traffic["reference_audit"])
    t_ref = time.perf_counter()
    mismatches, compared, first = verify.compare(expected, items, answered,
                                                 audit)
    ref_s = time.perf_counter() - t_ref
    failed = sum(r.error is not None for r in run.requests)
    checks = {
        "verdict_mismatches": {"value": mismatches, "limit": 0},
        "reference_vs_known": {"value": expected.disagreements, "limit": 0},
        "failed_requests": {"value": failed, "limit": 0},
    }
    correct = bool(run.requests) and all(
        c["value"] <= c["limit"] for c in checks.values())
    walls = sorted(r.t1 - r.t0 for r in run.requests)
    print(json.dumps({
        "window": {"requests": len(walls), "window_s": run.window_s,
                   "compiles": run.window_compiles,
                   "request_s": [walls[0], walls[len(walls) // 2],
                                 walls[-1]] if walls else None,
                   "gc_s": sum(t for _g, t in gc_pauses),
                   "gc_gen2_s": [t for g, t in gc_pauses if g == 2],
                   "spans_dropped": run.spans_dropped,
                   "slow_requests": slow_requests(run.requests)},
        "verify": {"verdicts_compared": compared, "first_mismatches": first,
                   "reference_runs": expected.reference_runs,
                   "reference_s": ref_s}}), flush=True)

    # -- metrics
    metrics = {}
    wanted = cell.per_layer if args.trace else cell.end_to_end
    kind_dir = "metrics" if args.trace else "end_to_end"
    for m in wanted:
        value = reader(kind_dir, m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(run.requests),
              "failed": failed, "metrics": metrics, "device": device}
    if args.trace and run.profile is not None:
        busy_s, window_s = trace_mod.busy_and_window_s(run.profile)
        device["busy_s"] = busy_s
        device["window_s"] = window_s
        result["breakdown"] = {
            "device_ops": trace_mod.top_ops(run.profile),
            "idle_gaps": trace_mod.idle_gaps(
                run.profile, host_spans(run, run.profile)),
        }
    result["checks"] = checks
    return result, checks


def host_spans(run, red):
    """The program's obs spans, every one, and the harness's requests on
    the trace's clock, named for the idle-gap breakdown: a span by its
    own name (``checker/split``), but for three the engine's."""
    traced = run.requests[:len(red["requests"])]
    offsets = sorted(t0 - req.mono0 for (t0, _e, _i), req
                     in zip(red["requests"], traced))
    if not offsets:
        return []
    off = offsets[len(offsets) // 2]
    out = [(s, e, "request: harness and checker seam")
           for s, e, _ in red["requests"]]
    for s in run.spans:
        if s.get("t1") is None:
            continue
        out.append((s["t0"] + off, s["t1"] + off,
                    {"engine/pipeline": "engine host: encode, plan, settle",
                     "engine/dispatch": "engine: waiting on a dispatch",
                     "engine/oracle": "CPU oracle"}.get(s["name"],
                                                        s["name"])))
    return out


def main(argv=None):
    try:
        result, checks = run_cell(argv)
    except (RunError, ImportError) as e:
        print(f"perf/run.py: {e}", file=sys.stderr)
        return 2
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
