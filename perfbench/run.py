"""The benchmark's one command.

    python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chips: turn on the bytecode cache, reach
the TPU or fail, turn on the persistent compile cache, let the cell's
driver build its inputs from the seed and warm up its programs (set-up),
run the cell's batches for ``--seconds`` (nothing may compile there),
then check what the timed programs produce against the plain reference,
and print one JSON line.

This file knows no cell, configuration or metric by name.  A cell named
in ``BENCHMARK.json`` has ``workloads/<cell>.json``; that names its
configuration, ``configs/<config>.json``, which names its driver and its
reference; a per-layer metric has ``layer_metrics/<name>.py``.
"""

import time

_T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from dataclasses import dataclass  # noqa: E402

if __package__ in (None, ""):  # run as a file: make the checkout importable
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from perfbench.harness import files, stats  # noqa: E402

CACHE_DIR = ".jax_cache"  # fixed: the path is part of the cache's key
PYCACHE_DIR = ".pycache"  # CPython's bytecode, beside the executables
TRACE_DIR = ".perfbench_trace"
# The TPU runtime pins a 4 GiB host buffer for transfers while it starts:
# 7 s of jax.devices() on a v5e's host, and up to 11 s once the machine has
# run a few processes (PERF.md Finding 10).  The benchmark asks for 256 MiB:
# 1.7 s, the same in every run.  The two job cells move data to the host
# inside their windows (a snapshot of 77.76 MB a call, a save of 2.49 GB
# every 48 calls) and live within it by their own bounds: `output.ahead_bytes`
# 160e6 (two snapshots asked for at once) and the library's `AHEAD_BYTES`
# 16e6; more than the buffer asked for at once moves at 0.35 GB/s and stalls
# the device's queue (PERF.md, PRs 34 and 36).
PREMAPPED_BYTES = 256 << 20


@dataclass
class Sample:
    row: str
    start: float
    end: float

    @property
    def seconds(self):
        return self.end - self.start


@dataclass
class Context:
    """What a driver's ``setup`` is given."""

    config: dict
    workload: dict
    seed: int
    devices: list
    bench_dir: pathlib.Path = files.BENCH_DIR


@dataclass
class View:
    """What a per-layer metric's reader is given."""

    session: object
    facts: dict
    samples: list
    traced: list
    trace: object
    probe: dict
    compile: dict
    peaks: dict
    setup: dict


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def runtime_environment():
    """Before jax starts the TPU runtime; what the caller has set stays.
    The runtime's own logs would go to the fixed path /tmp/tpu_logs, which
    two checkouts would share: they are turned off."""
    os.environ.setdefault("TPU_PREMAPPED_BUFFER_SIZE", str(PREMAPPED_BYTES))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def enable_bytecode_cache(root):
    """Before jax is imported.  Set-up is judged warm: as the compiled
    programs come from ``CACHE_DIR`` from a tree's second run on, what
    the run imports comes from bytecode under ``PYCACHE_DIR``, a fixed
    path in the checkout, or under the caller's ``PYTHONPYCACHEPREFIX``,
    which stays.  The image sets ``PYTHONDONTWRITEBYTECODE=1`` and, on
    the chip's machine, has no bytecode outside the standard library,
    so every run would compile from source what it imports (jax: 2.8 s;
    ``jax.experimental.pallas``: 1.2 s more; PERF.md Finding 11).  That
    is a default of the image, not a choice of the caller, and with it
    on the cache cannot exist: it is overridden, for this process only.
    A tree's first run compiles and writes; the others read."""
    sys.dont_write_bytecode = False
    if not os.environ.get("PYTHONPYCACHEPREFIX"):
        sys.pycache_prefix = str(pathlib.Path(root) / PYCACHE_DIR)


def enable_compile_cache(root):
    """The persistent cache at ``JAX_COMPILATION_CACHE_DIR`` if that is
    set, else at a fixed path in the checkout; every program is cached,
    however fast it compiled (jax's default leaves out those under 1 s,
    which is every collective program)."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir", str(pathlib.Path(root) / CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def require_chips(chips):
    """The TPU's devices, or no run: never a fallback."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"perfbench: no TPU: jax.devices()[0].platform is "
            f"{devices[0].platform!r}; a device metric needs the chip")
    if len(devices) < chips:
        raise SystemExit(
            f"perfbench: the cell needs {chips} chips, jax sees {len(devices)}")
    return devices


def describe_devices(devices, used):
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in devices[:used]
    ]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(peaks),
    }


def run_window(session, cycle, seconds, meter, trace_dir=None, trace_rows=()):
    """The measured window: batches in the cycle's order until the time
    is up.  With ``trace_dir``, the profiler is on over the window's
    first part, the batches ``trace_rows``, and those batches
    are kept apart; the time the profiler takes to start and to write
    its trace is not the window's.  Returns the window's start, the
    samples, the traced samples, the failures and the compilations."""
    import jax

    from perfbench.harness import trace as tracing

    samples, traced, failed = [], [], 0

    def one(row, into):
        nonlocal failed
        start = time.perf_counter()
        try:
            session.batch(row)
        except Exception:  # a failed batch is counted, and fails the run
            traceback.print_exc()
            failed += 1
            return
        into.append(Sample(row, start, time.perf_counter()))

    compiles_before = meter.compiles
    t_start = time.perf_counter()
    profiler_s = 0.0
    if trace_dir:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        t_traced = time.perf_counter()
        with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
            for row in trace_rows:
                one(row, traced)
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        profiler_s = (t_traced - t_start) + (time.perf_counter() - t_stop)
        print(f"perfbench: traced {len(traced)} batches in "
              f"{t_stop - t_traced:.3f} s; the profiler took {profiler_s:.3f} s "
              "to start and to write", flush=True)
    i = 0
    while (time.perf_counter() - t_start - profiler_s < seconds
           and failed == 0):
        one(cycle[i % len(cycle)], samples)
        i += 1
    compiled = meter.compiles - compiles_before
    return t_start, samples, traced, failed, compiled


def run_cell(args, devices, root=files.ROOT, bench_dir=files.BENCH_DIR):
    """Everything after the look for a chip.  Returns the result line."""
    from perfbench.harness import trace as tracing
    from perfbench.harness.compilemeter import CompileMeter
    from perfbench.harness.peaks import peaks_for
    from perfbench.harness.spans import HOST_SPANS

    benchmark = files.load_benchmark(root)
    cell = files.find_cell(benchmark, args.workload)
    workload = files.load_json("workloads", cell["name"], bench_dir)
    config = files.load_json("configs", cell["config"], bench_dir)
    for key in ("config", "chips", "traffic"):
        if workload[key] != cell[key]:
            raise files.BenchmarkFileError(
                f"workloads/{cell['name']}.json and BENCHMARK.json differ "
                f"on {key!r}: {workload[key]!r} != {cell[key]!r}")
    meter = CompileMeter().start()
    ctx = Context(config, workload, args.seed, list(devices),
                  pathlib.Path(bench_dir))
    t_driver = time.perf_counter()  # the driver's module loads the program
    driver = files.load_module("drivers", config["driver"], bench_dir)
    session = driver.setup(ctx)
    print(f"perfbench: set-up: {t_driver - _T0:.3f} s to reach the chips, "
          f"{time.perf_counter() - t_driver:.3f} s in the driver", flush=True)
    cycle = stats.schedule(workload["rows"], args.seed)
    setup_compile = meter.snapshot()

    trace_dir, trace_rows = None, ()
    if args.trace:
        trace_dir = str(pathlib.Path(root) / TRACE_DIR)
        shutil.rmtree(trace_dir, ignore_errors=True)
        # `trace_batches` batches of every row, one unless the row says
        # otherwise (0 for a row whose events would swamp the trace)
        rows = {r["name"]: r for r in workload["rows"]}
        trace_rows = [r for r in dict.fromkeys(cycle)
                      for _ in range(rows[r].get("trace_batches", 1))]
    t_start, samples, traced, failed, compiled = run_window(
        session, cycle, args.seconds, meter, trace_dir, trace_rows)
    setup = {"setup_s": t_start - _T0, "after_chips_s": t_start - t_driver}
    device = describe_devices(devices, cell["chips"])
    print(f"perfbench: {len(samples)} batches in the window, {failed} failed, "
          f"{compiled} compilations inside it (limit 0)", flush=True)

    metrics, extra = {}, {}
    end_to_end = dict(session.end_to_end(samples) if samples else {},
                      setup_s=setup["setup_s"])
    if args.trace:
        t_read = time.perf_counter()
        reduced = tracing.read_xplane(tracing.find_xplane(trace_dir), HOST_SPANS)
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"perfbench: reading the trace took "
              f"{time.perf_counter() - t_read:.3f} s", flush=True)
        device["busy_s"] = tracing.busy_s(reduced)
        device["window_s"] = tracing.window_s(reduced)
        extra["breakdown"] = tracing.breakdown(reduced, HOST_SPANS)
        probe = session.layer_probe() if hasattr(session, "layer_probe") else {}
        view = View(session, session.facts(), samples, traced, reduced, probe,
                    setup_compile, peaks_for(device["kind"]), setup)
        for entry in files.metrics_of(benchmark, "per_layer", cell["name"]):
            reader = files.load_module("layer_metrics", entry["name"], bench_dir)
            value = reader.read(view)
            if value is not None:
                metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    else:
        for entry in files.metrics_of(benchmark, "end_to_end", cell["name"]):
            if entry["name"] not in end_to_end:
                raise RuntimeError(
                    f"the window gave no {entry['name']}: a row it is taken "
                    "over has no batch there; no result")
            metrics[entry["name"]] = {
                "value": end_to_end[entry["name"]], "unit": entry["unit"]}
    for name, value in sorted(end_to_end.items()):
        print(f"perfbench: {name} = {value!r}", flush=True)

    t_check = time.perf_counter()
    checks = session.check()
    print(f"perfbench: the comparison took {time.perf_counter() - t_check:.3f} s",
          flush=True)
    correct = failed == 0 and compiled == 0 and bool(samples)
    compared = {}  # every number beside its limit: the line's last key
    for c in checks:
        ok = c["value"] <= c["limit"]
        correct = correct and ok
        compared[c["name"]] = {"value": c["value"], "limit": c["limit"]}
        print(f"perfbench: check {c['name']}: {c['value']!r} against the "
              f"limit {c['limit']!r}: {'ok' if ok else 'NOT CORRECT'}",
              file=sys.stderr, flush=True)
    return {"correct": correct,
            "attempted": len(samples) + len(traced) + failed, "failed": failed,
            "metrics": metrics, "device": device, **extra, "checks": compared}


def main(argv=None):
    args = parse_args(argv)
    benchmark = files.load_benchmark()
    cell = files.find_cell(benchmark, args.workload)
    enable_bytecode_cache(files.ROOT)
    runtime_environment()
    import jax

    enable_compile_cache(files.ROOT)
    devices = require_chips(cell["chips"])
    print(f"perfbench: {args.workload} seed {args.seed} on {len(devices)} x "
          f"{devices[0].device_kind} ({devices[0].platform}), jax {jax.__version__}",
          flush=True)
    result = run_cell(args, devices)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
