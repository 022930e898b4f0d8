"""Multi-process job launcher: the ``mpirun`` equivalent.

    python -m mpi4jax_tpu.launch -np 4 prog.py [args...]

Spawns N worker processes, wires the DCN-bridge bootstrap environment
(T4J_RANK / T4J_SIZE / T4J_COORD), initialises the native runtime in
each child before handing control to the user program, and propagates
the first failure (terminating the rest) — the fail-fast job semantics
of ``mpirun`` + the reference's MPI_Abort behaviour.  The summary
names WHICH rank failed first and how (nonzero exit vs. signal kill),
and a dying child broadcasts an abort to its peers first so survivors
raise a contextual error instead of hanging until the kill
(docs/failure-semantics.md).

``--timeout SECONDS`` adds a whole-job deadline: past it the job is
torn down and the launcher exits 124, naming the ranks that were still
running (the likely hang participants).

``--restarts N`` adds bounded auto-relaunch: a job that exits nonzero
(other than Ctrl-C) is relaunched up to N more times with a fresh
coordinator port and job id, the attempt count and final status
reported per attempt.  This is the coarse-grained rung under the
transport's fine-grained self-healing (docs/failure-semantics.md):
pair it with ``utils/checkpoint.py`` so the relaunched job resumes at
the last saved step instead of from scratch.

``--telemetry DIR`` turns on comm telemetry for every rank
(``T4J_TELEMETRY=trace`` unless the environment already chose a mode,
docs/observability.md): each rank drains its native event ring +
metrics snapshot into ``DIR/rank<k>.t4j.json`` at exit — on the abort
path too, so a dying rank's last events reach the first-failure
report — and after the job the launcher merges the per-rank files into
one Perfetto-loadable ``DIR/job.trace.json`` with all ranks on one
aligned timeline.  Inspect with ``t4j-top DIR`` or load the merged
trace at https://ui.perfetto.dev.

It also arms the crash-consistent flight recorder (``T4J_FLIGHT=on``
into ``DIR`` unless the environment explicitly chose, docs/
observability.md "flight recorder"): each rank's event ring + metrics
table live in an mmap'd ``DIR/rank<k>-<boot>.t4jflight`` file, so a
rank killed by SIGKILL / segfault / OOM — which never runs any drain —
still leaves its last events on disk.  On a failed job the launcher
runs ``t4j-postmortem DIR`` and prints the verdict (first-failing
rank, its last in-flight op, the affected links, and how the death
ordered against any elastic resize) under the first-failure report.

Children default to the CPU platform (one XLA CPU per process, the
reference's process model); override with ``--platform``.
"""

import argparse
import os
import signal
import socket
import subprocess
import sys
import threading
import time


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _say(msg):
    print(f"mpi4jax_tpu.launch: {msg}", file=sys.stderr, flush=True)


def _swallow(fn):
    """Run ``fn`` ignoring every failure (best-effort side work, e.g.
    the exit-time metrics scrape — it must never take the job down)."""
    try:
        fn()
    except Exception:
        pass


def _acquire_devices():
    """Initialise the worker's jax backend (a seam the tests fake)."""
    import jax

    return jax.devices()


def child_main(argv):
    """Entry for worker processes (internal)."""
    prog, *prog_args = argv
    platform = os.environ.get("T4J_PLATFORM")
    if platform and platform != "default":
        import jax

        jax.config.update("jax_platforms", platform)
    size = int(os.environ.get("T4J_SIZE", "1"))
    if platform != "cpu" and size > 1:
        # An accelerator chip belongs to one process at a time and this
        # launcher pins no chip to a worker, so in a world larger than
        # one all but the first worker can fail to get the device.  Find
        # out now, before the bootstrap waits for peers that will die.
        try:
            _acquire_devices()
        except RuntimeError as exc:
            raise SystemExit(
                f"mpi4jax_tpu.launch: rank {os.environ.get('T4J_RANK', '?')}"
                f" of {size} could not get a device of platform "
                f"{platform!r}: {exc}\n"
                "An accelerator chip belongs to one process at a time, and "
                "this launcher does not pin chips to workers (no "
                "TPU_VISIBLE_* or process-bounds handling): every worker "
                "of --platform default/tpu sees the whole host's chips. "
                "Run accelerator workers with -np 1, or keep the workers "
                "on --platform cpu."
            ) from exc
    from mpi4jax_tpu.native import runtime

    runtime.ensure_initialized()
    sys.argv = [prog] + prog_args
    import runpy

    try:
        runpy.run_path(prog, run_name="__main__")
    except BaseException as e:
        # the MPI_Abort analog: tell peers this rank is going down so
        # their blocked collectives raise within their deadline instead
        # of hanging until the launcher's terminate
        code = e.code if isinstance(e, SystemExit) else None
        if not (isinstance(e, SystemExit) and code in (0, None)):
            why = (
                f"rank {os.environ.get('T4J_RANK', '?')} died: "
                f"{type(e).__name__}: {e}"
            )
            try:
                runtime.notify_abort(why)
            except Exception:
                pass
            # drain telemetry NOW (not only at atexit): a rank about to
            # be signal-killed by the launcher's teardown would lose
            # its ring, and the dying rank's last events are the most
            # valuable part of the first-failure report
            tel_dir = os.environ.get("T4J_TELEMETRY_DIR")
            if tel_dir:
                try:
                    from mpi4jax_tpu.telemetry import dump

                    dump.write_rank_file(tel_dir)
                except Exception:
                    pass
            # first-failure report: when the self-healing transport saw
            # action before the death, say so — a rank dying AFTER
            # surviving reconnects usually points at a flaky fabric
            try:
                stats = runtime.link_stats()
                if stats and stats["reconnects"]:
                    print(
                        f"r{os.environ.get('T4J_RANK', '?')} | t4j link "
                        f"stats at failure: {stats['reconnects']} "
                        f"reconnect(s), {stats['replayed_frames']} "
                        f"frame(s) / {stats['replayed_bytes']} bytes "
                        "replayed (docs/failure-semantics.md)",
                        file=sys.stderr,
                        flush=True,
                    )
            except Exception:
                pass
        raise


def _describe_exit(rc):
    """Human-readable child status: signal kills are reported
    distinctly from nonzero exits (satellite: fail-fast summary)."""
    if rc is not None and rc < 0:
        try:
            name = signal.Signals(-rc).name
        except ValueError:
            name = f"signal {-rc}"
        return f"killed by {name} (signal {-rc})"
    return f"exited with code {rc}"


def _job_exit_code(rc):
    """Normalise a child status into a valid launcher exit code:
    signal-killed children map to the shell convention 128+signum."""
    if rc is None:
        return 1
    if rc < 0:
        return 128 - rc  # rc = -signum
    return rc


def main(argv=None):
    parser = argparse.ArgumentParser(prog="mpi4jax_tpu.launch")
    parser.add_argument("-np", "--nprocs", type=int, required=False)
    parser.add_argument(
        "--platform",
        default="cpu",
        help="jax platform to pin workers to (default: cpu). Pass "
        "'default' to leave the environment's platform untouched — "
        "e.g. to run a worker against a real accelerator. A chip "
        "belongs to one process at a time and no chip is pinned to a "
        "worker, so an accelerator platform needs -np 1; with more, a "
        "worker that cannot get the device exits at once and says so.",
    )
    parser.add_argument(
        "--shims",
        action="store_true",
        help="prepend the mpi4py/mpi4jax import shims to the workers' "
        "PYTHONPATH (run unmodified reference programs)",
    )
    parser.add_argument(
        "--timeout",
        type=float,
        default=None,
        metavar="SECONDS",
        help="whole-job deadline: past it every worker is torn down and "
        "the launcher exits 124, naming the ranks still running",
    )
    parser.add_argument(
        "--restarts",
        type=int,
        default=0,
        metavar="N",
        help="bounded auto-relaunch: a job exiting nonzero (other than "
        "Ctrl-C) is relaunched up to N more times with a fresh "
        "coordinator/job id — pair with utils/checkpoint.py so the "
        "relaunch resumes at the last saved step",
    )
    parser.add_argument(
        "--telemetry",
        default=None,
        metavar="DIR",
        help="comm telemetry (docs/observability.md): every rank "
        "drains its event ring into DIR/rank<k>.t4j.json at exit "
        "(T4J_TELEMETRY=trace unless the environment already set a "
        "mode), and the launcher merges them into a Perfetto-loadable "
        "DIR/job.trace.json; inspect with t4j-top DIR",
    )
    parser.add_argument(
        "--metrics",
        type=int,
        default=None,
        metavar="PORT",
        help="live metrics exporter (docs/observability.md): rank k "
        "serves its metrics snapshot + link stats on 127.0.0.1:PORT+k "
        "(/metrics Prometheus text, /metrics.json), and the launcher "
        "serves the aggregated job view — worst-link and straggler "
        "gauges — on PORT+nprocs",
    )
    parser.add_argument(
        "--elastic",
        choices=("shrink", "rejoin"),
        default=None,
        metavar="MODE",
        help="elastic world membership (docs/failure-semantics.md "
        "\"elastic membership\"): a dead rank no longer takes the job "
        "down — survivors agree on a reduced world and continue "
        "(shrink), and with MODE=rejoin the launcher relaunches ONLY "
        "the dead slot (T4J_REJOIN=1) so the replacement re-bootstraps "
        "into the mesh at the next epoch fence.  Sets T4J_ELASTIC for "
        "every rank; T4J_MIN_WORLD floors the shrink.  Composes with "
        "--restarts: the whole world restarts only when the job "
        "actually failed (e.g. it fell below T4J_MIN_WORLD).",
    )
    parser.add_argument(
        "--autoscale",
        action="store_true",
        help="traffic-driven elastic autoscaling (docs/serving.md "
        "\"Autoscaling\"): sets T4J_AUTOSCALE=on and a grow-request "
        "file (T4J_AUTOSCALE_REQ) for every rank.  The serving "
        "leader's policy posts grow requests to the file; the "
        "launcher answers by relaunching retired slots as "
        "T4J_REJOIN=1 expansion ranks through rank 0's kept-open "
        "coordinator port.  A follower exiting cleanly while the "
        "leader serves on is a scaledown (the in-band retire plan), "
        "recorded in the membership history, and its slot is reused "
        "by the next grow.  Requires --elastic rejoin.",
    )
    parser.add_argument(
        "--autotune",
        action="store_true",
        help="calibrate the data-plane knob vector at init "
        "(docs/performance.md \"trace-guided autotuning\"): every rank "
        "runs a few collective timing rounds, the fit is persisted in "
        "the topology-fingerprinted tuning cache (T4J_TUNING_CACHE) "
        "and applied to this job; later jobs on the same fabric load "
        "it automatically.  Explicit T4J_* knob env vars still win.",
    )
    parser.add_argument(
        "--serve",
        action="store_true",
        help="serving-job wiring (docs/serving.md): sets T4J_ADMIT=on "
        "for every rank (deadline-aware admission control with "
        "honest shed accounting) unless the environment explicitly "
        "chose, pair with --slo for the latency target.  The program "
        "is expected to run a mpi4jax_tpu.serving engine "
        "(benchmarks/serving.py is the reference loop).",
    )
    parser.add_argument(
        "--slo",
        type=float,
        default=None,
        metavar="MS",
        help="with --serve: per-request end-to-end latency SLO in "
        "milliseconds (T4J_SLO_MS for every rank; admission sheds "
        "predicted misses instead of blowing the p99)",
    )
    parser.add_argument(
        "--max-batch",
        type=int,
        default=None,
        metavar="N",
        help="with --serve: concurrent decode slots in the serving "
        "engine's KV pool (T4J_MAX_BATCH)",
    )
    parser.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    parser.add_argument("prog", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)

    if args.child:
        child_main(args.prog)
        return 0

    if not args.nprocs or not args.prog:
        parser.error("usage: python -m mpi4jax_tpu.launch -np N prog.py ...")
    if args.timeout is not None and args.timeout <= 0:
        parser.error("--timeout must be > 0 seconds (omit it for no deadline)")
    if args.restarts < 0:
        parser.error("--restarts must be >= 0")
    if args.metrics is not None and not (
        1 <= args.metrics and args.metrics + args.nprocs < 65536
    ):
        parser.error(
            "--metrics PORT must leave room for nprocs+1 ports below "
            "65536"
        )
    if args.slo is not None and not args.serve:
        parser.error("--slo requires --serve (it sets the serving "
                     "engine's T4J_SLO_MS)")
    if args.max_batch is not None and not args.serve:
        parser.error("--max-batch requires --serve (it sets the "
                     "serving engine's T4J_MAX_BATCH)")
    if args.slo is not None and args.slo <= 0:
        parser.error("--slo must be > 0 milliseconds (omit it for no "
                     "SLO)")
    if args.autoscale and args.elastic != "rejoin":
        parser.error("--autoscale requires --elastic rejoin (a grow "
                     "admits replacement ranks through the kept-open "
                     "coordinator port)")

    attempts = args.restarts + 1
    for attempt in range(1, attempts + 1):
        exit_code = _run_job(args)
        if exit_code == 0 or exit_code == 130:
            break
        if attempt < attempts:
            _say(
                f"attempt {attempt}/{attempts} exited with code "
                f"{exit_code}; restarting the job "
                f"({attempts - attempt} restart(s) left)"
            )
        elif args.restarts:
            # without --restarts the launcher's failure output must
            # stay exactly the pre-restart-feature report
            _say(
                f"attempt {attempt}/{attempts} exited with code "
                f"{exit_code}; restart budget exhausted (--restarts "
                f"{args.restarts})"
            )
    if args.restarts and exit_code == 0 and attempt > 1:
        _say(f"job succeeded on attempt {attempt}/{attempts}")
    return exit_code


def _flight_dir(tel_dir):
    """Where the children actually wrote their flight files: spawn()
    lets an explicit ambient T4J_FLIGHT_DIR win over the telemetry
    dir, so the post-mortem readers must follow the same choice."""
    return os.environ.get("T4J_FLIGHT_DIR", "").strip() or tel_dir


def _telemetry_failure_report(tel_dir, rank):
    """Print the dying rank's last telemetry events under the
    first-failure line — the post-mortem shows WHAT the rank was
    doing, not just that it died.  Prefers the drained rank file (the
    abort path wrote it); a hard-killed rank never drained, so fall
    back to its crash-consistent flight-recorder file, whose mmap'd
    ring survived the kill (docs/observability.md "flight
    recorder")."""
    try:
        from mpi4jax_tpu.native.runtime import _format_recent_events
        from mpi4jax_tpu.telemetry import dump, schema

        path = os.path.join(tel_dir, dump.rank_file_name(rank))
        events = []
        source = "drained"
        if os.path.exists(path):
            obj = schema.load_rank_file(path)
            events = [schema.event_from_list(r)
                      for r in obj["events"][-8:]]
        else:
            fdir = _flight_dir(tel_dir)
            flights = sorted(
                f for f in os.listdir(fdir)
                if f.startswith(f"rank{rank}-")
                and f.endswith(".t4jflight")
            )
            if not flights:
                return
            obj = schema.read_flight_file(
                os.path.join(fdir, flights[-1]))
            events = obj["events"][-8:]
            source = "flight recorder"
        tail = _format_recent_events(events)
        if tail:
            _say(f"rank {rank} last telemetry events ({source}): {tail}")
    except Exception:
        pass  # the report must never mask the real failure


def _postmortem_report(tel_dir):
    """Run the cross-rank death analysis over the drained + flight
    files and print the verdict under the first-failure report: WHO
    failed first, its last in-flight op/step, the affected links, each
    peer's view, and the death-vs-resize ordering (t4j-postmortem's
    summary, docs/observability.md "flight recorder")."""
    try:
        from mpi4jax_tpu.telemetry import postmortem

        # stale_s=0: every child has been reaped by now, so a fresh
        # heartbeat only dates the death — it cannot mean "alive"
        fdir = _flight_dir(tel_dir)
        report = postmortem.analyze_dir(tel_dir, stale_s=0.0,
                                        flight_dir=fdir)
        for line in postmortem.summary_lines(report):
            _say(f"postmortem: {line}")
        extra = f" --flight-dir {fdir}" if fdir != tel_dir else ""
        _say(f"postmortem: full report: t4j-postmortem {tel_dir}{extra}")
    except Exception:
        pass  # best-effort: never mask the real failure


def _merge_telemetry(tel_dir, job):
    try:
        from mpi4jax_tpu.telemetry import trace

        out = trace.merge_dir(tel_dir, job=job)
        _say(
            f"telemetry merged into {out} (load in "
            "https://ui.perfetto.dev, or run: t4j-top "
            f"{tel_dir})"
        )
    except FileNotFoundError:
        _say(f"telemetry: no rank files appeared in {tel_dir}")
    except Exception as e:
        _say(f"telemetry merge failed: {type(e).__name__}: {e}")


def _start_job_metrics(port, n, job):
    """Serve the aggregated job metrics view on ``port + n``: each
    scrape of the job endpoint scrapes every rank's ``/metrics.json``
    (ranks that have not bootstrapped yet, or died, simply drop out of
    ``ranks_reporting``) and aggregates — no polling thread, the
    freshness is the scraper's.  Returns the exporter or None."""
    try:
        from mpi4jax_tpu.telemetry import exporter

        def collect():
            snaps = []
            for r in range(n):
                try:
                    snaps.append(exporter.scrape(
                        f"http://127.0.0.1:{port + r}/metrics.json",
                        timeout=0.5,
                    ))
                except Exception:
                    continue
            if not snaps:
                return None
            agg = exporter.aggregate_snapshots(snaps, job=job)
            # the exit-time summary runs after the rank endpoints are
            # gone: remember the freshest live view any scrape saw
            srv.last_agg = agg
            return agg

        srv = exporter.MetricsExporter(port + n, collect_fn=collect)
        srv.last_agg = None
        srv.start()
        _say(
            f"job metrics on http://127.0.0.1:{port + n}/metrics "
            f"(per-rank: ports {port}..{port + n - 1})"
        )
        return srv
    except Exception as e:  # noqa: BLE001 — metrics must not kill the launch
        _say(f"job metrics aggregator failed: {type(e).__name__}: {e}")
        return None


def _load_autoscale_module():
    """The pure scale-policy module holds the request-file protocol
    (serving/autoscale.py).  Importing it through the package trips
    the jax version gate on old-jax containers, so fall back to
    loading the file directly — it only needs the stdlib."""
    try:
        from mpi4jax_tpu.serving import autoscale

        return autoscale
    except Exception:
        import importlib.util

        path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "serving", "autoscale.py",
        )
        spec = importlib.util.spec_from_file_location(
            "_t4j_launch_autoscale", path
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


def _run_job(args):
    """One launch attempt: spawn the workers, wait, fail fast."""
    n = args.nprocs
    coord = f"127.0.0.1:{_free_port()}"
    # unique job id: namespaces the bridge's same-host shm segments so
    # concurrent/successive jobs can never collide on stale segments
    import uuid

    job = uuid.uuid4().hex[:12]
    tel_dir = None
    if args.telemetry:
        tel_dir = os.path.abspath(args.telemetry)
        os.makedirs(tel_dir, exist_ok=True)
    metrics_srv = None
    if args.metrics is not None:
        metrics_srv = _start_job_metrics(args.metrics, n, job)
    autoscale_api = None
    autoscale_req = None
    if args.autoscale:
        import tempfile

        autoscale_api = _load_autoscale_module()
        # per-job request file: the leader posts grow requests here
        # (T4J_AUTOSCALE_REQ), the poll loop below consumes them
        autoscale_req = os.path.join(
            tempfile.gettempdir(), f"t4j-scale-{job}.json"
        )
    def spawn(rank, rejoin=False):
        env = dict(os.environ)
        env.update(
            T4J_RANK=str(rank),
            T4J_SIZE=str(n),
            T4J_COORD=coord,
            T4J_PLATFORM=args.platform,
            T4J_JOB=job,
        )
        if args.elastic:
            env["T4J_ELASTIC"] = args.elastic
        if args.autoscale:
            env["T4J_AUTOSCALE"] = "on"
            env["T4J_AUTOSCALE_REQ"] = autoscale_req
        if rejoin:
            # replacement slot: re-bootstrap through rank 0's kept-open
            # coordinator port instead of the full-world rendezvous
            env["T4J_REJOIN"] = "1"
        if tel_dir:
            env["T4J_TELEMETRY_DIR"] = tel_dir
            # trace unless the caller already chose a mode (counters
            # keeps the overhead at metrics-only for perf runs)
            env.setdefault("T4J_TELEMETRY", "trace")
            # crash-consistent flight recorder: without it a
            # SIGKILL'd/segfaulted rank loses its entire ring — and
            # that is the rank every postmortem needs.  An explicit
            # ambient T4J_FLIGHT (off included) still wins.
            env.setdefault("T4J_FLIGHT", "on")
            env.setdefault("T4J_FLIGHT_DIR", tel_dir)
        if args.autotune:
            env["T4J_AUTOTUNE"] = "1"
        if args.serve:
            # serving wiring (docs/serving.md): admission on unless
            # the environment explicitly chose (off included — the
            # uncontrolled-baseline arm of the benchmarks)
            env.setdefault("T4J_ADMIT", "on")
            if args.slo is not None:
                env["T4J_SLO_MS"] = str(args.slo)
            if args.max_batch is not None:
                env["T4J_MAX_BATCH"] = str(args.max_batch)
        if args.metrics is not None:
            env["T4J_METRICS_PORT"] = str(args.metrics)
            # the exporter serves the metrics table + link stats —
            # counters mode records them at <=5% overhead; an explicit
            # ambient choice (off included) still wins
            env.setdefault("T4J_TELEMETRY", "counters")
        if args.shims:
            from mpi4jax_tpu import shims

            env["PYTHONPATH"] = shims.path() + os.pathsep + env.get(
                "PYTHONPATH", ""
            )
        cmd = [
            sys.executable,
            "-m",
            "mpi4jax_tpu.launch",
            "--child",
            *args.prog,
        ]
        return subprocess.Popen(cmd, env=env)

    procs = [spawn(rank) for rank in range(n)]

    exit_code = 0
    start = time.monotonic()
    terminated_at = None  # first terminate time, for SIGKILL escalation
    elastic = args.elastic
    # membership bookkeeping for the elastic summary: the launcher's
    # view of the epoch history (boot -> shrink -> rejoin -> ...),
    # printed next to the children's link-stats dumps at job end
    epoch_guess = 0
    members = n
    history = [f"boot({n})"]
    exited_ok = set()
    last_bad_rc = None
    relaunches = 0
    scaled_down = []  # slots the autoscaler retired; reused by grows
    last_scale_poll = 0.0

    try:
        remaining = set(range(n))
        final_scrape_started = False
        while remaining:
            for i in list(remaining):
                rc = procs[i].poll()
                if rc is None:
                    continue
                remaining.discard(i)
                if metrics_srv is not None and remaining \
                        and not final_scrape_started:
                    # first exit: the surviving ranks still serve —
                    # grab one job view (off-loop: the serial 0.5 s/
                    # rank scrape must not delay the fail-fast kill
                    # below) so the exit-time summary has data even
                    # when nothing external ever scraped
                    final_scrape_started = True
                    threading.Thread(
                        target=lambda: _swallow(metrics_srv.collect),
                        daemon=True,
                    ).start()
                if rc == 0:
                    exited_ok.add(i)
                    if (autoscale_req and i != 0 and 0 in remaining
                            and exit_code == 0
                            and terminated_at is None):
                        # a clean follower exit while the leader serves
                        # on is the autoscaler's in-band retire plan,
                        # not a fault: record the scaledown (the
                        # survivors' native layer is committing the
                        # smaller world right now) and keep the slot
                        # for a later grow
                        epoch_guess += 1
                        members -= 1
                        scaled_down.append(i)
                        history.append(
                            f"e{epoch_guess}:scaledown({members}) "
                            f"[rank {i} retired at "
                            f"+{time.monotonic() - start:.1f}s]"
                        )
                        _say(
                            f"rank {i} retired by the autoscaler — "
                            f"{members} rank(s) serving"
                        )
                    continue
                if elastic and exit_code == 0 and terminated_at is None:
                    # elastic membership: a dead rank is a shrink, not
                    # the job's end — the survivors' native layer is
                    # agreeing on the reduced world right now
                    last_bad_rc = rc
                    epoch_guess += 1
                    members -= 1
                    history.append(
                        f"e{epoch_guess}:shrink({members}) "
                        f"[rank {i} {_describe_exit(rc)} at "
                        f"+{time.monotonic() - start:.1f}s]"
                    )
                    _say(
                        f"rank {i} {_describe_exit(rc)} — elastic "
                        f"{args.elastic}: {len(remaining)} rank(s) "
                        "continue"
                    )
                    if tel_dir:
                        _telemetry_failure_report(tel_dir, i)
                    if (args.elastic == "rejoin" and i != 0
                            and relaunches < n):
                        # relaunch ONLY the dead slot; the replacement
                        # re-bootstraps via the incarnation handshake
                        # and joins at the next epoch fence.  (A dead
                        # rank 0 cannot rejoin — it owns the
                        # coordinator port — so its world stays
                        # shrunk.)
                        relaunches += 1
                        epoch_guess += 1
                        members += 1
                        history.append(
                            f"e{epoch_guess}:rejoin({members}) "
                            f"[rank {i} relaunched]"
                        )
                        _say(f"relaunching rank {i} as a rejoin "
                             f"replacement ({relaunches} so far)")
                        procs[i] = spawn(i, rejoin=True)
                        remaining.add(i)
                    continue
                if rc != 0 and exit_code == 0:
                    exit_code = _job_exit_code(rc)
                    # fail fast: take the rest of the job down, and say
                    # WHO failed first and HOW — the post-mortem anchor
                    _say(
                        f"rank {i} {_describe_exit(rc)} — first failure; "
                        f"terminating {len(remaining)} remaining rank(s)"
                    )
                    if tel_dir:
                        _telemetry_failure_report(tel_dir, i)
                    terminated_at = time.monotonic()
                    for j in remaining:
                        procs[j].terminate()
            if remaining:
                now = time.monotonic()
                if (autoscale_req and exit_code == 0
                        and terminated_at is None
                        and now - last_scale_poll > 0.5):
                    # answer the serving leader's grow requests: each
                    # retired slot relaunches as a T4J_REJOIN=1
                    # expansion rank (one epoch per admit).  Malformed
                    # or stale files are consumed and ignored —
                    # read_request never raises.
                    last_scale_poll = now
                    req = autoscale_api.read_request(autoscale_req)
                    if req is not None:
                        autoscale_api.clear_request(autoscale_req)
                        want = min(int(req["want_world"]), n)
                        scaled_down.sort()
                        while (members < want and scaled_down
                               and relaunches < 4 * n):
                            slot = scaled_down.pop(0)
                            relaunches += 1
                            epoch_guess += 1
                            members += 1
                            history.append(
                                f"e{epoch_guess}:grow({members}) "
                                f"[rank {slot} relaunched: "
                                f"{req['reason'] or 'grow request'}]"
                            )
                            _say(
                                f"autoscale grow to {want}: "
                                f"relaunching rank {slot} as an "
                                f"expansion rank ({members} serving)"
                            )
                            exited_ok.discard(slot)
                            procs[slot] = spawn(slot, rejoin=True)
                            remaining.add(slot)
                if (
                    args.timeout is not None
                    and exit_code == 0
                    and now - start > args.timeout
                ):
                    exit_code = 124
                    still = ", ".join(str(i) for i in sorted(remaining))
                    _say(
                        f"job deadline of {args.timeout:g}s exceeded; "
                        f"rank(s) {still} still running — terminating "
                        "the job"
                    )
                    terminated_at = now
                    for j in remaining:
                        procs[j].terminate()
                if terminated_at is not None and now - terminated_at > 10:
                    # a worker wedged in native code can ignore SIGTERM
                    # forever; escalate so the launcher itself cannot hang
                    for j in remaining:
                        procs[j].kill()
                time.sleep(0.05)
    except KeyboardInterrupt:
        for p in procs:
            p.send_signal(signal.SIGINT)
        exit_code = 130
    if elastic and exit_code == 0:
        # the job succeeded iff the final membership — every rank that
        # was not declared dead — finished cleanly and stayed at or
        # above the floor; below it, the nonzero code flows into
        # --restarts' whole-world relaunch
        try:
            from mpi4jax_tpu.utils import config as _config

            floor = _config.min_world()
        except Exception:
            # an unparsable floor already failed every child loudly at
            # ensure_initialized; the summary check degrades quietly
            floor = 1
        if len(exited_ok) < max(floor, 1):
            exit_code = _job_exit_code(last_bad_rc)
            _say(
                f"only {len(exited_ok)} rank(s) finished cleanly — "
                f"below T4J_MIN_WORLD={floor}; the elastic world did "
                "not survive"
            )
    if elastic and exit_code != 130:
        # the membership/epoch history, next to the children's
        # link-stats dumps: the post-mortem (or success report) shows
        # how the world evolved, not just how it ended
        final = sorted(exited_ok) if exited_ok else []
        _say("world membership history: " + " -> ".join(history))
        _say(
            f"final world membership: {len(final)}/{n} rank(s) "
            f"[{', '.join(str(r) for r in final)}] after "
            f"{epoch_guess} membership epoch(s)"
        )
    if metrics_srv is not None:
        # the workers have exited, so their endpoints are gone — a
        # fresh scrape can only come up empty; fall back to the
        # freshest live view any scrape cached so the job's final
        # straggler / worst-link line still lands in the launch log
        try:
            agg = metrics_srv.collect() or getattr(
                metrics_srv, "last_agg", None
            )
            if agg:
                worst = agg["worst_link"]
                where = (f" (rank {worst['rank']})"
                         if worst["rank"] is not None else "")
                _say(
                    f"job metrics final: {agg['ranks_reporting']} "
                    f"rank(s) reporting, straggler="
                    f"{agg['straggler'] if agg['straggler'] is not None else 'n/a'}, "
                    f"worst link reconnects={worst['reconnects']}"
                    + where
                )
        except Exception:
            pass
        metrics_srv.stop()
    if autoscale_req:
        # consume any request posted after the last poll: a leftover
        # file would leak into the temp dir (the job id namespaces it,
        # so a successor job can never mistake it for its own)
        autoscale_api.clear_request(autoscale_req)
    if tel_dir and exit_code != 130:
        # cross-rank death analysis from the drained + flight files:
        # on a failed job it names the first failure; on an elastic
        # job that shrank-and-survived it documents the departures
        # next to the membership history above
        if exit_code != 0 or (elastic and epoch_guess > 0):
            _postmortem_report(tel_dir)
        _merge_telemetry(tel_dir, job)
    return exit_code


if __name__ == "__main__":
    sys.exit(main())
