#!/usr/bin/env bash
# CI smoke: the three lanes that were previously run by hand, in one
# script (exit nonzero on the first failing lane).
#
#   1. tier-1  — the ROADMAP.md sweep (fast tests, CPU platform)
#   2. fault   — the fault-injection suite (multi-process jobs that
#                kill/stall/isolate ranks; docs/failure-semantics.md)
#   3. proc    — the multi-process DCN-bridge lane (tests/proc/, auto-
#                marked by its conftest), fault tests excluded since
#                lane 2 just ran them
#   4. asan    — AddressSanitizer BUILD check of the native bridge
#                (T4J_SANITIZE=address; the cached .so rebuilds because
#                the sanitize flag is part of the build fingerprint).
#                Running the suites under ASan needs LD_PRELOAD plumbing
#                (.claude/skills/verify/SKILL.md) and stays manual.
#   5. tsan    — same BUILD check under ThreadSanitizer
#                (T4J_SANITIZE=thread): the bridge's progress/abort/shm
#                threads compile under the race instrumentation.
#   6. lint    — tools/lint.sh: ruff + mypy (pyproject.toml config) and
#                t4j-lint over examples/ + models/, so the contract
#                analyzer dogfoods the repo's own programs on every run
#                (docs/static-analysis.md).  Tools missing from the
#                container are skipped inside lint.sh.  The t4j leg
#                gates on the --format json exit_code field, so a
#                crashed analyzer fails the lane distinctly from
#                findings.
#   6b. verify — tools/verify_smoke.py: the cross-rank schedule
#                simulator (docs/static-analysis.md T4J010-T4J014).
#                Seeded hazard matrix (all five rule classes must
#                fire, clean ring/halo/hier/overlap shapes must not),
#                a recorded two-rank serving plan stream replayed
#                clean plus a corrupted-digest drift, and — on
#                new-jax containers — t4j-verify over the repo's own
#                lint entries.  Pure core, runs everywhere.
#   7. resilience — tools/resilience_smoke.py under the ASan build: an
#                8-rank flaky-fault job (rank 1 drops every connection
#                twice mid-allreduce) must self-heal to bit-identical
#                results with zero aborts, and the same drop with
#                T4J_RETRY_MAX=0 must fail stop (docs/
#                failure-semantics.md "self-healing transport").  Runs
#                the ctypes data plane directly, so it works on
#                old-jax containers and computes its own sanitizer
#                LD_PRELOAD.  The self-heal phase runs with telemetry
#                tracing on and asserts the reconnects appear as ring
#                events (docs/observability.md).
#   8. telemetry — tools/telemetry_smoke.py under the ASan build: an
#                8-rank trace-mode job whose ranks drain their event
#                rings (drained events monotone + begin/end complete),
#                merged into one job.trace.json that must validate
#                against the trace schema with all ranks on one
#                aligned timeline and render through t4j-top; plus an
#                off-mode phase that must drain ZERO events
#                (docs/observability.md).  ctypes only — runs on
#                old-jax containers.
#   9. async   — tools/async_smoke.py three times over: plain, under
#                AddressSanitizer, and under ThreadSanitizer (the
#                progress thread is exactly what TSan exists for).
#                8-rank nonblocking matrix (iallreduce/isend/irecv/
#                ireduce_scatter bit-identical to blocking, out-of-
#                order waits, overlapping requests, parked irecv,
#                test/double-wait/unknown-id semantics) plus a
#                request-leak phase asserting the finalize report
#                (docs/async.md).  ctypes only — runs on old-jax
#                containers.
#  10. diagnose — tools/diagnose_smoke.py twice: plain and under
#                AddressSanitizer.  An 8-rank trace job with step
#                markers and ONE rank slowed by T4J_FAULT_MODE=delay:
#                t4j-diagnose --json must finger that rank as the
#                straggler in >= 9/10 steps with a "wire" attribution,
#                the per-step overlap ratio must agree with the
#                harness's ground truth, and every rank's exporter
#                endpoint must serve a schema-valid snapshot
#                (docs/observability.md "diagnosing a slow step").
#                ctypes only — runs on old-jax containers.
#  12. elastic — tools/elastic_smoke.py twice: plain and under
#                AddressSanitizer.  Elastic world membership
#                (docs/failure-semantics.md "elastic membership"):
#                an 8-rank job loses a rank mid-collective and
#                completes at 7 (T4J_ELASTIC=shrink, shm and TCP
#                transports), a shrink below T4J_MIN_WORLD aborts
#                naming the floor, T4J_ELASTIC=off reproduces the
#                legacy abort report byte-for-byte, and a relaunched
#                replacement re-bootstraps through the kept-open
#                coordinator port and rejoins at epoch 2
#                (T4J_ELASTIC=rejoin).  ctypes only — runs on old-jax
#                containers.
#  14. postmortem — tools/postmortem_smoke.py twice: plain and under
#                AddressSanitizer.  The crash-consistent flight
#                recorder (docs/observability.md "flight recorder"):
#                an 8-rank T4J_FLIGHT=on job whose victim rank
#                SIGKILLs itself mid-collective must leave a
#                recoverable mmap'd flight file (unfinalized header,
#                stopped heartbeat, the open allreduce still in the
#                ring), and t4j-postmortem must name the victim, its
#                in-flight op and the affected links from the
#                persisted files alone; a clean run must finalize
#                every header with zero false deaths, and an
#                unset-knob run must write no flight files.  ctypes
#                only — runs on old-jax containers.
#  15. stripe — tools/stripe_smoke.py three times over: plain, ASan,
#                and TSan (stripe readers/writers/repair dialers are
#                exactly the concurrency TSan exists for; the
#                throttle perf phase auto-skips under sanitizers).
#                Striped multi-connection links
#                (docs/performance.md "striped links and the
#                zero-copy path"): stripe-width matrix (2/3/8) with
#                ring + tiny-p2p ordering checks, a one-stripe kill
#                (T4J_FAULT_STRIPE) that must self-heal per stripe
#                with siblings never breaking, MSG_ZEROCOPY
#                armed-or-loud-degrade, the byte-stable T4J_STRIPES=1
#                legacy path, and the emulated multi-flow busbw step
#                (>= 1.25x at 4 stripes under T4J_EMU_FLOW_BPS).
#                Plus one striped elastic shrink run
#                (T4J_STRIPES=2 elastic_smoke) so the resize path
#                stays green over striped links.  ctypes only — runs
#                on old-jax containers.
#  17. compress — tools/compress_smoke.py twice: plain and under
#                AddressSanitizer.  Compressed collectives
#                (docs/performance.md "Compressed collectives") over
#                the real native bridge with T4J_EMU_LOCAL=1 (one
#                emulated host per rank, so the every-hop-cross-host
#                predicate engages): the cast-fused bf16/fp8 ring
#                against the f32 oracle within the documented
#                quantisation tolerance with BIT-identical results
#                across ranks and the logical/wire byte counters
#                proving the 2x/4x saving, the byte-stable
#                T4J_WIRE_DTYPE=off contract (bit-identical, counters
#                zero), and the flow-capped off-vs-bf16 interleaved
#                busbw step (>= 1.4x gate; auto-skips under
#                sanitizers).  ctypes only — runs on old-jax
#                containers.
#  16. serving — tools/serving_smoke.py twice: plain and under
#                AddressSanitizer.  The continuous-batching serving
#                control plane (docs/serving.md) over the real native
#                bridge: an 8-rank Poisson burst past capacity with
#                admission ON must shed (counted, never swallowed)
#                while every rank executes the digest-checked
#                broadcast step plans and converges to the identical
#                completion sequence, then drain to zero
#                queued/active requests at exit; an admission-OFF
#                phase must complete everything with zero sheds.
#                ctypes + the jax-free serving pure core only — runs
#                on old-jax containers.
#  19. autoscale — tools/autoscale_smoke.py twice: plain and under
#                AddressSanitizer.  Epoch-safe elastic serving
#                (docs/failure-semantics.md "serving epoch survival",
#                docs/serving.md "Autoscaling"): a 4-rank seeded
#                Poisson ramp survives a mid-decode SIGKILL of a
#                FOLLOWER (the leader rides the resize and reissues
#                every in-flight request) and of the LEADER itself
#                (the lowest survivor promotes from its plan mirror
#                and drains the reissued requests), with the
#                accounting invariant (queued + in_slots + done +
#                shed + reissued == submitted) checked on every step
#                of every epoch and zero aborts; then a no-fault
#                phase where the real Autoscaler decides a
#                drain-then-shrink and the in-band plan retire flag
#                walks the cascade one rank per epoch (4 -> 3 -> 2),
#                retirees exiting rc 0.  ctypes + the jax-free
#                serving pure core only — runs on old-jax containers.
#  13. autotune — tools/autotune_smoke.py twice: plain and under
#                AddressSanitizer.  An 8-rank calibrate phase (the
#                collective knob fit measured through the telemetry
#                metrics table must converge to ONE vector across
#                ranks and persist to the fingerprint-keyed cache)
#                followed by a reload phase (cache-loaded knobs with
#                per-knob provenance, explicit T4J_SEG_BYTES beating
#                the cache, and the fused gather-send/scatter-recv +
#                fused-alltoall paths bit-identical to per-part
#                frames; docs/performance.md "trace-guided
#                autotuning").  ctypes + the jax-free tuning package
#                only — runs on old-jax containers.
#  18. uring  — tools/uring_smoke.py three times over: plain, ASan,
#                and TSan (the completion-driven engine fold is
#                exactly the concurrency TSan exists for; the perf
#                phase auto-skips under sanitizers).  The io_uring
#                wire backend (docs/performance.md "io_uring wire
#                backend"): forced-unsupported probe must degrade
#                LOUDLY to sendmsg, an 8-rank striped ring must be
#                bit-identical on both backends with live syscall
#                counters, registered-buffer fixed I/O must survive
#                replay-ring eviction and a killed-stripe self-heal
#                under uring, idle ranks must not spin on either
#                backend (adaptive io tick), and the interleaved
#                small-frame arms must show uring cutting syscalls
#                per call without a p50 regression.  On kernels
#                without io_uring the uring phases skip loudly and
#                the degrade contract still runs.  ctypes only —
#                runs on old-jax containers.
#
# Usage: tools/ci_smoke.sh [lane...]   (default: all lanes)

set -uo pipefail
cd "$(dirname "$0")/.."

lanes=("$@")
if [ ${#lanes[@]} -eq 0 ]; then
  lanes=(tier1 fault proc asan tsan lint verify resilience telemetry
         async diagnose elastic autotune postmortem stripe
         serving autoscale compress uring)
fi

run_lane() {
  echo "=== lane: $1 ==="
  shift
  "$@"
  rc=$?
  if [ $rc -ne 0 ]; then
    echo "=== lane FAILED (rc=$rc) ==="
    exit $rc
  fi
}

for lane in "${lanes[@]}"; do
  case "$lane" in
    tier1)
      # the ROADMAP.md tier-1 command, verbatim semantics: fast tests,
      # collection errors tolerated (old-jax containers skip heavily)
      run_lane tier1 env JAX_PLATFORMS=cpu timeout -k 10 870 \
        python -m pytest tests/ -q -m 'not slow' \
        --continue-on-collection-errors -p no:cacheprovider \
        -p no:xdist -p no:randomly
      ;;
    fault)
      run_lane fault env JAX_PLATFORMS=cpu timeout -k 10 1200 \
        python -m pytest tests/ -q -m fault \
        --continue-on-collection-errors -p no:cacheprovider \
        -p no:xdist -p no:randomly
      ;;
    proc)
      run_lane proc env JAX_PLATFORMS=cpu timeout -k 10 1800 \
        python -m pytest tests/proc -q -m 'proc and not fault and not slow' \
        --continue-on-collection-errors -p no:cacheprovider \
        -p no:xdist -p no:randomly
      ;;
    asan)
      run_lane asan env T4J_SANITIZE=address \
        python -m mpi4jax_tpu.native.build
      ;;
    tsan)
      run_lane tsan env T4J_SANITIZE=thread \
        python -m mpi4jax_tpu.native.build
      ;;
    lint)
      run_lane lint tools/lint.sh
      ;;
    verify)
      # the cross-rank schedule simulator dogfooded over seeded
      # hazards, a recorded serving plan stream, and (new-jax
      # containers) the repo's own lint entries
      run_lane verify env JAX_PLATFORMS=cpu timeout -k 10 600 \
        python tools/verify_smoke.py
      ;;
    resilience)
      run_lane resilience env T4J_SANITIZE=address timeout -k 10 900 \
        python tools/resilience_smoke.py 8
      run_lane resilience-uring env -u T4J_SANITIZE \
        T4J_WIRE_BACKEND=uring timeout -k 10 900 \
        python tools/resilience_smoke.py 8
      ;;
    telemetry)
      run_lane telemetry env T4J_SANITIZE=address timeout -k 10 900 \
        python tools/telemetry_smoke.py 8
      ;;
    async)
      run_lane async-plain env -u T4J_SANITIZE timeout -k 10 900 \
        python tools/async_smoke.py 8
      run_lane async-asan env T4J_SANITIZE=address timeout -k 10 900 \
        python tools/async_smoke.py 8
      run_lane async-tsan env T4J_SANITIZE=thread timeout -k 10 1800 \
        python tools/async_smoke.py 4
      ;;
    diagnose)
      run_lane diagnose-plain env -u T4J_SANITIZE timeout -k 10 900 \
        python tools/diagnose_smoke.py 8
      run_lane diagnose-asan env T4J_SANITIZE=address timeout -k 10 900 \
        python tools/diagnose_smoke.py 8
      ;;
    elastic)
      run_lane elastic-plain env -u T4J_SANITIZE timeout -k 10 1200 \
        python tools/elastic_smoke.py 8
      run_lane elastic-asan env T4J_SANITIZE=address timeout -k 10 1800 \
        python tools/elastic_smoke.py 8
      ;;
    autotune)
      run_lane autotune-plain env -u T4J_SANITIZE timeout -k 10 900 \
        python tools/autotune_smoke.py 8
      run_lane autotune-asan env T4J_SANITIZE=address timeout -k 10 900 \
        python tools/autotune_smoke.py 8
      ;;
    postmortem)
      run_lane postmortem-plain env -u T4J_SANITIZE timeout -k 10 900 \
        python tools/postmortem_smoke.py 8
      run_lane postmortem-asan env T4J_SANITIZE=address timeout -k 10 900 \
        python tools/postmortem_smoke.py 8
      ;;
    stripe)
      run_lane stripe-plain env -u T4J_SANITIZE timeout -k 10 1200 \
        python tools/stripe_smoke.py 8
      run_lane stripe-asan env T4J_SANITIZE=address timeout -k 10 1800 \
        python tools/stripe_smoke.py 8
      run_lane stripe-tsan env T4J_SANITIZE=thread timeout -k 10 1800 \
        python tools/stripe_smoke.py 4
      run_lane stripe-elastic env -u T4J_SANITIZE T4J_STRIPES=2 \
        timeout -k 10 1200 python tools/elastic_smoke.py 8
      run_lane stripe-uring env -u T4J_SANITIZE \
        T4J_WIRE_BACKEND=uring timeout -k 10 1200 \
        python tools/stripe_smoke.py 8
      ;;
    serving)
      run_lane serving-plain env -u T4J_SANITIZE timeout -k 10 900 \
        python tools/serving_smoke.py 8
      run_lane serving-asan env T4J_SANITIZE=address timeout -k 10 900 \
        python tools/serving_smoke.py 8
      ;;
    autoscale)
      run_lane autoscale-plain env -u T4J_SANITIZE timeout -k 10 1200 \
        python tools/autoscale_smoke.py 4
      run_lane autoscale-asan env T4J_SANITIZE=address timeout -k 10 1800 \
        python tools/autoscale_smoke.py 4
      ;;
    compress)
      run_lane compress-plain env -u T4J_SANITIZE timeout -k 10 1200 \
        python tools/compress_smoke.py 8
      run_lane compress-asan env T4J_SANITIZE=address timeout -k 10 1800 \
        python tools/compress_smoke.py 8
      ;;
    uring)
      run_lane uring-plain env -u T4J_SANITIZE timeout -k 10 1200 \
        python tools/uring_smoke.py 8
      run_lane uring-asan env T4J_SANITIZE=address timeout -k 10 1800 \
        python tools/uring_smoke.py 8
      run_lane uring-tsan env T4J_SANITIZE=thread timeout -k 10 1800 \
        python tools/uring_smoke.py 4
      ;;
    *)
      echo "unknown lane: $lane (want tier1|fault|proc|asan|tsan|lint|resilience|telemetry|async|diagnose|elastic|autotune|postmortem|stripe|serving|autoscale|compress|uring)" >&2
      exit 2
      ;;
  esac
done
echo "=== all lanes passed ==="
