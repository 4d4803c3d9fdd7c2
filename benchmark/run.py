"""Run one benchmark cell once.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is data: ``BENCHMARK.json`` names its
configuration (``benchmark/configs/<name>.json``) and its traffic mix
(``benchmark/traffic/<name>.json``); the configuration names its
entry-point driver (``benchmark/drivers/<name>.py``); each per-layer
metric is a reader of its own (``benchmark/metrics/<name>.py``).  This
file holds only the CLI and the window, the device check, the trace
and the last line.

Set-up (process start to the window's start) generates the traffic from
the seed, builds the system and warms every shape the traffic uses.  The
window then runs for ``--seconds``; compiles inside it are counted and
printed.  After the window the device memory peak is read, the system's
state is freed, and the driver compares what the window produced with
the plain reference (``benchmark/reference.py``).  The last line of
standard output is one JSON object; with ``--trace 1`` it carries the
per-layer metrics instead of the end-to-end ones.

``--rehearse`` (never used by the driver) runs at the configuration's
tiny size on whatever JAX finds, labels the result ``rehearsal`` and
reports no device metric.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".bench_cache")
WORK_DIR = os.path.join(ROOT, ".bench_run")


class BenchError(Exception):
    """The run cannot produce a result (no chip, bad cell, ...)."""


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(name):
    """(benchmark, workload entry, configuration, traffic) of a cell."""
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(path):
        raise BenchError("no BENCHMARK.json next to benchmark/")
    bench = load_json(path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     cell["traffic"] + ".json"))
    return bench, cell, config, traffic


def metrics_for(bench, cell_name, section):
    return [m for m in bench[section]
            if "workloads" not in m or cell_name in m["workloads"]]


def pin_caches():
    """JAX's persistent cache (and the program's executable store, which
    follows it) at a fixed path inside the checkout: only the first run
    of a cell in a checkout compiles."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE_DIR, "jax")
    os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


class CompileCounter:
    """Counts XLA backend compiles in this process (JAX's own event)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1


def device_check(chips, rehearse):
    """The devices JAX finds; a run without the chips it asks for ends
    here with no result."""
    import jax

    devs = jax.devices()
    if not rehearse:
        if devs[0].platform != "tpu":
            raise BenchError(f"JAX found no TPU (platform {devs[0].platform})")
        if len(devs) < chips:
            raise BenchError(f"the cell needs {chips} chips, JAX sees "
                             f"{len(devs)}")
    return devs


def device_facts(devs):
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def peaks_of(kind):
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def read_per_layer(bench, cell_name, ctx):
    out = {}
    for m in metrics_for(bench, cell_name, "per_layer"):
        mod = load_module(os.path.join(BENCH, "metrics", m["name"] + ".py"),
                          "bench_metric_" + m["name"].replace(".", "_"))
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(args):
    bench, cell, config, traffic = cell_spec(args.workload)
    pin_caches()
    sys.path.insert(0, ROOT)
    sys.path.insert(0, BENCH)
    if importlib.util.find_spec("logparser_tpu") is None:
        raise BenchError("the program (logparser_tpu) is not in this checkout")
    driver_mod = load_module(
        os.path.join(BENCH, "drivers", config["driver"] + ".py"),
        "bench_driver_" + config["driver"])
    shutil.rmtree(WORK_DIR, ignore_errors=True)
    os.makedirs(WORK_DIR)
    driver = driver_mod.Driver(config, traffic, args.seed, args.seconds,
                               WORK_DIR, rehearse=args.rehearse)
    try:
        return run_driver(args, bench, cell, driver)
    finally:
        driver.close()
        shutil.rmtree(WORK_DIR, ignore_errors=True)


def counters():
    from logparser_tpu.observability import metrics

    return metrics().snapshot()


def run_driver(args, bench, cell, driver):
    from check import verdict

    devs = device_check(cell["chips"], args.rehearse)
    counter = CompileCounter()
    driver.setup()
    trace_dir = os.path.join(WORK_DIR, "trace")
    tracing = bool(args.trace) and not args.rehearse
    if tracing:
        from logparser_tpu.observability import enable_stage_annotations

        enable_stage_annotations()
    before = counters()
    compiles0 = counter.n
    setup_s = time.monotonic() - T_START
    log(f"setup_s {setup_s:.4f}")
    if tracing:
        import jax

        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation("bench.window"):
            result = driver.window(args.seconds)
        jax.profiler.stop_trace()
    else:
        result = driver.window(args.seconds)
    after = counters()
    log(f"compiles_in_window {counter.n - compiles0}")
    device = device_facts(devs)
    driver.free()
    checks = driver.check()
    correct = verdict(checks)
    for line in driver.notes():
        log(line)

    if args.trace:
        trace_mod = load_module(os.path.join(BENCH, "trace.py"), "bench_trace")
        reduced = trace_mod.reduce_trace(trace_dir) if tracing else None
        if reduced is not None:
            log("trace modules %s" % json.dumps(reduced["module_s"]))
        ctx = {"before": before, "after": after, "facts": result,
               "trace": reduced, "device": device,
               "peaks": peaks_of(device["kind"]) if not args.rehearse else None}
        metrics = read_per_layer(bench, cell["name"], ctx)
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
    else:
        metrics = {}
        for m in metrics_for(bench, cell["name"], "end_to_end"):
            value = setup_s if m["name"] == "setup_s" else result.get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": bool(correct), "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics, "device": device}
    if args.trace and tracing and reduced is not None:
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    if args.rehearse:
        line["rehearsal"] = "CPU rehearsal at a tiny size: no device metric"
    line["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    return line


def become_subreaper():
    """Orphaned descendants (a helper's own children) reparent to this
    process, so ``reap_children`` finds them."""
    import ctypes

    try:
        ctypes.CDLL(None, use_errno=True).prctl(36, 1, 0, 0, 0)  # PR_SET_CHILD_SUBREAPER
    except (OSError, AttributeError):
        pass


def child_pids():
    me, out = os.getpid(), []
    for d in os.listdir("/proc"):
        if not d.isdigit():
            continue
        try:
            with open(f"/proc/{d}/stat") as f:
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
        except (OSError, IndexError, ValueError):
            continue
        if ppid == me:
            out.append(int(d))
    return out


def reap_children(grace_s=10.0):
    """Stop multiprocessing's forkserver and resource tracker (they would
    outlive this process by a moment), then SIGTERM, and after
    ``grace_s`` SIGKILL, every child still here, and wait for each."""
    import signal

    try:
        import multiprocessing.forkserver as fs
        import multiprocessing.resource_tracker as rt

        fs._forkserver._stop()
        rt._resource_tracker._stop()
    except Exception as e:  # noqa: BLE001 — the kill below still runs
        log(f"cleanup: stopping multiprocessing helpers failed: {e!r}")
    for sig in (signal.SIGTERM, signal.SIGKILL, signal.SIGKILL):
        left = child_pids()
        if not left:
            return []
        for pid in left:
            try:
                os.kill(pid, sig)
            except ProcessLookupError:
                pass
        deadline = time.monotonic() + grace_s
        while left and time.monotonic() < deadline:
            for pid in list(left):
                try:
                    if os.waitpid(pid, os.WNOHANG)[0]:
                        left.remove(pid)
                except ChildProcessError:
                    left.remove(pid)
            time.sleep(0.05)
    return child_pids()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    become_subreaper()
    try:
        line = run(args)
    except BenchError as e:
        log(f"FAILED: {e}")
        return 1
    finally:
        left = reap_children()
    if left:
        log(f"FAILED: processes {left} could not be stopped")
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
