"""Continuous-batching serving benchmark: p50/p99 latency, rps, shed
rate and goodput-under-SLO under open-loop Poisson load
(docs/serving.md "measuring it").

The latency-bound companion to the throughput benches: a
tensor-parallel transformer served by ``mpi4jax_tpu.serving`` on the
proc tier, driven by a seeded open-loop load generator.  Run under the
launcher::

    python -m mpi4jax_tpu.launch -np 8 benchmarks/serving.py \\
        --arms pairs --slo 4000

``--arms pairs`` (default) interleaves an **admission-on** and an
**admission-off** window back to back, repeatedly, with the SAME
seeded arrival stream per window — the interleaved same-conditions
convention of every A/B bench in this repo.  The off arm measures
(but never enforces) the same SLO, so the records show both what
admission control delivered and what the uncontrolled baseline did to
the p99.  The injected-straggler demo is env-driven, exactly like the
PR-8 diagnosis tests::

    T4J_FAULT_MODE=delay T4J_FAULT_RANK=3 T4J_FAULT_DELAY_MS=80 \\
        python -m mpi4jax_tpu.launch --telemetry /tmp/serve \\
        -np 8 benchmarks/serving.py --arms pairs --slo 6000

(the records then carry ``fault_mode``/``fault_rank`` labels, and the
``--telemetry`` dir feeds ``t4j-diagnose``, which attributes the
baseline's p99 blowup to the delayed rank's wire phase).

Open-loop, on purpose: a closed-loop generator waits for completions
before sending more, so an overloaded server sees its own arrival
rate collapse and the measured p99 flatters it (the classic
coordinated-omission trap).  Open-loop arrivals keep coming at the
configured rate; an overloaded admission-on server SHEDS (counted),
an overloaded baseline QUEUES (p99 blows up) — both outcomes are the
measurement.

``--arms ramp`` is the elastic contrast (docs/serving.md
"Autoscaling"): an **auto** arm (the engine's traffic-driven scale
policy armed — run under ``launch.py --autoscale --elastic rejoin``
so grow requests spawn real T4J_REJOIN=1 ranks and in-band retires
shrink the world back) against a **static** arm serving the SAME
seeded piecewise Poisson ramp (``--ramp 1,10,1``) at the boot world.
The records carry SLO attainment for both arms plus
goodput-per-rank-second — SLO-met completions divided by the
rank-seconds that actually served them, integrated over the live
world as resizes land — and the membership history proving the
epochs::

    python -m mpi4jax_tpu.launch -np 8 --elastic rejoin --autoscale \\
        benchmarks/serving.py --arms ramp --ramp 1,10,1 --slo 4000

Rank 0 prints one JSON record per metric (the serving arms print
``serving_p50_ms_procN`` / ``serving_p99_ms_procN`` /
``serving_rps_procN`` / ``serving_shed_rate_procN`` /
``serving_slo_attainment_procN`` + the ``_admit_off`` contrasts; the
autoscale arm prints ``serving_autoscale_slo_attainment_procN`` /
``goodput_per_rank_second_{auto,static}_procN``).
"""

import argparse
import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def _build(args):
    import jax

    jax.config.update("jax_platforms", "cpu")

    import mpi4jax_tpu as m
    from mpi4jax_tpu.models import transformer as tfm
    from mpi4jax_tpu.serving import engine as eng

    comm = m.get_default_comm()
    cfg = tfm.TransformerConfig(
        vocab=args.vocab, d_model=args.d_model, layers=args.layers,
        heads=args.heads, kv_heads=args.kv_heads,
        head_dim=args.d_model // args.heads, d_ff=args.d_ff,
    )
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    engine = eng.ServingEngine(
        comm, cfg, params, max_len=args.max_len,
        max_batch=args.max_batch, admit="off", slo_ms=0.0,
        overlap=(args.overlap == "on"), markers=True,
    )
    return comm, cfg, params, engine


def _warmup(engine, args):
    """Compile every prefill bucket in the prompt range + the decode
    executable, and seed the SLO estimator with real step times —
    outside the measured windows."""
    from mpi4jax_tpu.serving.request import Request

    lo, hi = args.prompt
    buckets = set()
    p = lo
    while True:
        buckets.add(engine._prefill_bucket(p))
        if p >= hi:
            break
        p = min(hi, p * 2 if p > 1 else 2)
    rid = -1
    for i, b in enumerate(sorted(buckets)):
        p_len = min(b, args.max_len - 2)
        engine.offer(
            Request(rid - i, tuple(range(1, p_len + 1)), 3, 0.0), 0.0
        )
    engine.drain(now_ms_fn=lambda: 0.0, stop=False)
    engine.finished.clear()


def _window(engine, args, arm, arm_stats, window_idx):
    """One measured window of ``arm`` ('on'|'off'): fresh seeded
    arrival stream, real-time pacing, drain at the end (drain time
    counts into the tail latencies — queued work is not free)."""
    from mpi4jax_tpu.serving import LoadGen

    slo = float(args.slo)
    engine.reconfigure(
        arm, slo_ms=slo, rate_limit=args.rate_limit,
        stats=arm_stats[arm], measure_slo_ms=slo,
    )
    # both arms STAMP deadlines (the off arm measures the same SLO it
    # does not enforce)
    deadline = (lambda t: t + slo) if slo else (lambda t: None)
    gen = LoadGen(
        seed=args.seed + 1000 * window_idx, rate_rps=args.rate,
        prompt_len=("uniform", *args.prompt),
        max_new=("uniform", *args.new),
        vocab=args.vocab, deadline_fn=deadline,
    )
    t0 = time.perf_counter()
    now_ms = lambda: (time.perf_counter() - t0) * 1e3  # noqa: E731
    dur_ms = args.duration * 1e3
    offered = 0
    while True:
        now = now_ms()
        if now >= dur_ms:
            break
        for req in gen.until(now):
            engine.offer(req, now_ms())
            offered += 1
        engine.step(now_ms())
    engine.drain(now_ms_fn=now_ms, stop=False)
    wall_s = time.perf_counter() - t0
    return {"offered": offered, "wall_s": wall_s}


def _ramp_window(engine, args, arm, arm_stats, window_idx):
    """One ramp window of ``arm`` ('auto'|'static'): the SAME seeded
    piecewise-constant Poisson ramp (``--ramp`` rates split evenly
    over ``--duration``).  The auto arm arms the engine's traffic
    policy (``enable_autoscale``), feeds it a decision window every
    ``--scale-window`` seconds, and integrates rank-seconds over the
    LIVE world size as resizes land; the static arm serves the whole
    ramp at the boot world.  Returns offered count, wall, integrated
    rank-seconds, and the membership history ``[(t_s, world), ...]``."""
    from mpi4jax_tpu.serving import LoadGen

    slo = float(args.slo)
    engine.reconfigure(
        "off", slo_ms=slo, stats=arm_stats[arm], measure_slo_ms=slo,
    )
    if arm == "auto":
        engine.enable_autoscale()
    else:
        engine.disable_autoscale()
    deadline = (lambda t: t + slo) if slo else (lambda t: None)
    rates = args.ramp
    dur_ms = args.duration * 1e3
    seg_ms = dur_ms / len(rates)
    gens = [
        LoadGen(
            seed=args.seed + 1000 * window_idx + 17 * i,
            rate_rps=r, prompt_len=("uniform", *args.prompt),
            max_new=("uniform", *args.new), vocab=args.vocab,
            deadline_fn=deadline, start_ms=i * seg_ms,
        )
        for i, r in enumerate(rates)
    ]
    t0 = time.perf_counter()
    now_ms = lambda: (time.perf_counter() - t0) * 1e3  # noqa: E731
    win_ms = args.scale_window * 1e3
    offered = 0
    rank_s = 0.0
    last_ms = 0.0
    next_win = win_ms
    world = engine._alive_world()
    membership = [(0.0, world)]
    while True:
        now = now_ms()
        # rank-seconds integrate against the world that ACTUALLY
        # served the interval — the honest denominator for goodput
        w = engine._alive_world()
        rank_s += world * (now - last_ms) / 1e3
        if w != world:
            membership.append((round(now / 1e3, 2), w))
            world = w
        last_ms = now
        if now >= dur_ms:
            break
        for i, gen in enumerate(gens):
            seg_end = (i + 1) * seg_ms
            for req in gen.until(min(now, seg_end)):
                engine.offer(req, now_ms())
                offered += 1
        engine.step(now_ms())
        if arm == "auto" and now >= next_win:
            engine.autoscale_window(now)
            next_win += win_ms
    engine.drain(now_ms_fn=now_ms, stop=False)
    wall_s = time.perf_counter() - t0
    rank_s += world * (wall_s - last_ms / 1e3)
    engine.disable_autoscale()
    return {
        "offered": offered, "wall_s": wall_s, "rank_s": rank_s,
        "membership": membership,
    }


def _ramp_records(arm_stats, n, info, extra):
    """The autoscale-vs-static contrast records: SLO attainment of the
    elastic arm (with the static baseline inlined as a label) and
    goodput-per-rank-second for both arms — SLO-met completions over
    the rank-seconds that actually served them."""
    recs = []
    snaps = {arm: arm_stats[arm].snapshot() for arm in ("auto", "static")}
    rnd = lambda v: None if v is None else round(v, 4)  # noqa: E731
    auto, static = snaps["auto"], snaps["static"]
    recs.append({
        "metric": f"serving_autoscale_slo_attainment_proc{n}",
        "value": rnd(auto["slo_attainment"]), "unit": "fraction",
        "nprocs": n, "slo_ms": auto["slo_ms"],
        "static_slo_attainment": rnd(static["slo_attainment"]),
        "epochs_survived": auto["epochs_survived"],
        "reissued": auto["reissued"],
        "membership": info["auto"]["membership"], **extra,
    })
    for arm in ("auto", "static"):
        s = snaps[arm]
        rank_s = info[arm]["rank_s"] or 1e-9
        recs.append({
            "metric": f"goodput_per_rank_second_{arm}_proc{n}",
            "value": round(s["slo_ok"] / rank_s, 4),
            "unit": "req/(rank*s)", "nprocs": n,
            "slo_ok": s["slo_ok"], "completed": s["completed"],
            "rank_seconds": round(rank_s, 2),
            "wall_s": round(info[arm]["wall_s"], 2), **extra,
        })
    return recs


def _arm_records(stats, n, arm, walls, extra):
    s = stats.snapshot()
    offered = s["completed"] + s["shed"]
    wall = sum(walls) or 1e-9
    suffix = "" if arm == "primary" else f"_admit_{arm}"
    recs = []

    def rec(metric, value, unit, **kw):
        if value is None:
            return
        recs.append({
            "metric": metric, "value": value, "unit": unit,
            "nprocs": n, **extra, **kw,
        })

    rnd = lambda v: None if v is None else round(v, 3)  # noqa: E731
    rec(f"serving_p50_ms_proc{n}{suffix}", rnd(s["latency_p50_ms"]),
        "ms", admit=s["admit_mode"], completed=s["completed"])
    rec(f"serving_p99_ms_proc{n}{suffix}", rnd(s["latency_p99_ms"]),
        "ms", admit=s["admit_mode"], completed=s["completed"],
        slo_ms=s["slo_ms"])
    rec(f"serving_rps_proc{n}{suffix}",
        round(s["completed"] / wall, 3), "req/s",
        admit=s["admit_mode"], wall_s=round(wall, 3))
    rec(f"serving_shed_rate_proc{n}{suffix}",
        round(s["shed"] / offered, 4) if offered else None, "fraction",
        admit=s["admit_mode"], shed=s["shed"], offered=offered,
        shed_by_reason=s["shed_by_reason"])
    rec(f"serving_slo_attainment_proc{n}{suffix}",
        rnd(s["slo_attainment"]), "fraction", admit=s["admit_mode"],
        slo_ms=s["slo_ms"], slo_ok=s["slo_ok"], offered=offered)
    if s["slo_ms"]:
        p99 = s["latency_p99_ms"]
        rec(f"serving_slo_held_proc{n}{suffix}",
            (1 if p99 is not None and p99 <= s["slo_ms"] else 0),
            "bool", p99_ms=rnd(p99), slo_ms=s["slo_ms"])
    return recs


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--arms", choices=("pairs", "on", "off", "ramp"),
                    default="pairs")
    ap.add_argument("--ramp", type=lambda s: tuple(
        float(x) for x in s.split(",")), default=(1.0, 10.0, 1.0),
        help="piecewise arrival rates for --arms ramp, split evenly "
        "over --duration (default 1,10,1 rps)")
    ap.add_argument("--scale-window", type=float, default=1.0,
        help="autoscale decision-window cadence in seconds "
        "(ramp arm)")
    ap.add_argument("--windows", type=int, default=2,
                    help="window repetitions per arm")
    ap.add_argument("--duration", type=float, default=8.0,
                    help="seconds of open-loop load per window")
    ap.add_argument("--rate", type=float, default=6.0,
                    help="open-loop arrival rate, requests/s")
    ap.add_argument("--rate-limit", type=float, default=0.0,
                    help="admission token-bucket rate (0 = SLO gate "
                    "only)")
    ap.add_argument("--slo", type=float, default=4000.0,
                    help="end-to-end SLO in ms (0 = none)")
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=48)
    ap.add_argument("--prompt", type=lambda s: tuple(
        int(x) for x in s.split(",")), default=(2, 12),
        help="prompt-length uniform bounds lo,hi")
    ap.add_argument("--new", type=lambda s: tuple(
        int(x) for x in s.split(",")), default=(4, 16),
        help="output-length uniform bounds lo,hi")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--d-model", type=int, default=32)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--d-ff", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--overlap", choices=("on", "off"), default="on")
    ap.add_argument("--quick", action="store_true",
                    help="one short window per arm")
    args = ap.parse_args(argv)
    if args.quick:
        args.windows = 1
        args.duration = min(args.duration, 4.0)

    comm, cfg, params, engine = _build(args)
    n = comm.size
    from mpi4jax_tpu.serving.stats import ServingStats

    if not engine.is_leader:
        engine.run_follower()
        return 0

    if args.arms == "ramp":
        arms = ("auto", "static")
    else:
        arms = (("on", "off") if args.arms == "pairs"
                else (args.arms,))
    arm_stats = {
        arm: ServingStats(slo_ms=float(args.slo),
                          max_batch=args.max_batch,
                          admit_mode="off" if arm in ("auto", "static")
                          else arm)
        for arm in arms
    }
    _warmup(engine, args)
    walls = {arm: [] for arm in arms}
    ramp_info = {
        arm: {"rank_s": 0.0, "wall_s": 0.0, "membership": []}
        for arm in arms
    }
    for w in range(args.windows):
        for arm in arms:
            if args.arms == "ramp":
                info = _ramp_window(engine, args, arm, arm_stats, w)
                ramp_info[arm]["rank_s"] += info["rank_s"]
                ramp_info[arm]["membership"] = info["membership"]
            else:
                info = _window(engine, args, arm, arm_stats, w)
            walls[arm].append(info["wall_s"])
            ramp_info[arm]["wall_s"] += info["wall_s"]
            s = arm_stats[arm].snapshot()
            print(
                f"[serving] window {w} arm={arm}: offered "
                f"{info['offered']} completed {s['completed']} shed "
                f"{s['shed']} p99 {s['latency_p99_ms'] and round(s['latency_p99_ms'])} ms",
                file=sys.stderr, flush=True,
            )
    engine.stop()

    extra = {
        "rate_rps": args.rate, "windows": args.windows,
        "duration_s": args.duration, "max_batch": args.max_batch,
        "max_len": args.max_len, "overlap": args.overlap,
        "interleaved_pairs": args.arms == "pairs",
        "model": {
            "layers": args.layers, "d_model": args.d_model,
            "heads": args.heads, "vocab": args.vocab,
        },
    }
    fault = os.environ.get("T4J_FAULT_MODE", "").strip()
    if fault:
        extra["fault_mode"] = fault
        extra["fault_rank"] = os.environ.get("T4J_FAULT_RANK")
        extra["fault_delay_ms"] = os.environ.get("T4J_FAULT_DELAY_MS")
    records = []
    # the unsuffixed primary keys come from the admission-on arm when
    # it ran (that is the controlled configuration the SLO story is
    # about); a single off-arm run reports itself unsuffixed but
    # labeled admit=off
    if args.arms == "ramp":
        extra["ramp_rps"] = list(args.ramp)
        records = _ramp_records(arm_stats, n, ramp_info, extra)
        for rec in records:
            print(json.dumps(rec), flush=True)
        return 0
    if "on" in arm_stats:
        records += _arm_records(arm_stats["on"], n, "primary",
                                walls["on"], extra)
        if "off" in arm_stats:
            records += _arm_records(arm_stats["off"], n, "off",
                                    walls["off"], extra)
    else:
        records += _arm_records(arm_stats["off"], n, "primary",
                                walls["off"], extra)
    for rec in records:
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    from mpi4jax_tpu.utils.runtime import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
