"""A job's own host spans beside the device trace: which idle stretch of
the chip was whose.

The program keeps its host spans in memory (``SolverJob.spans()``,
``mpi4jax_tpu/utils/spans.py``) on ``time.perf_counter_ns()``, the clock
``run.py`` takes every batch's start and end on.  They reach the
device's clock in two hops, each made from what a reader's view holds
(``README.hostspans.md`` has the reasons):

1. *The job's clock to the profiler's host clock.*  A traced batch ends
   on ``perf_counter()`` (``view.traced[i].end``) within microseconds of
   the end of its ``sync`` span on the profiler's clock: the offset is
   the median of the differences, and their range is printed.
2. *The profiler's host clock to the device's, from both sides.*  No
   program of a batch starts on the device before that batch's
   ``enqueue`` span starts, and the program a batch's ``sync`` waits for
   (its last multistep) has ended when that ``sync`` ends.  Over the
   traced batches that is a lower and an upper bound on the offset: the
   bracket.  Its middle is used, its width is the resolution: an idle
   stretch shorter than that is given no name (``below_resolution``).

All of the window's idle, the head before the first device event and
the tail after the last included, is then split by what the job's main
thread was in at the time: the innermost span of the job; else the
harness's ``sync``; else nothing.

A program without spans (the parent of the PR that brought them), spans
dropped, or a trace that does not match the batches: every function
here returns ``None`` and says why; none raises for that.
"""

import collections
import statistics
from dataclasses import dataclass, field

from perfbench.harness import scopes, trace as tracing
from perfbench.harness.spans import ENQUEUE, SYNC

BELOW = "below_resolution"
NO_SPAN = "no_span"
LONG_BATCH_S = 3e-3  # over the median of the batches without a save
ISSUE = ("job/advance", "job/enqueue", "job/ask")  # what a call costs the loop
SAVE, FETCH, WRITE = "checkpoint/save", "checkpoint/fetch", "checkpoint/write"


def _say(why):
    print(f"perfbench: hostspans: {why}; nothing is reported", flush=True)


def job_spans(view):
    """The finished spans of the session's job, or ``None`` where the
    program keeps none or has dropped some."""
    job = getattr(view.session, "job", None)
    if not hasattr(job, "spans"):
        return _say("the program keeps no host spans")
    if job.trace.dropped:
        return _say(f"the job dropped {job.trace.dropped} spans")
    return job.spans()


# -- the clocks ---------------------------------------------------------


def host_offset(traced, syncs):
    """Hop one: ``(offset_ns, range_ns)``, what to add to a time on the
    job's clock (ns) for the profiler's host clock."""
    if not traced or len(traced) != len(syncs):
        return _say(f"{len(traced)} traced batches, {len(syncs)} sync spans")
    diffs = [s.end_ns - b.end * 1e9
             for b, s in zip(traced, sorted(syncs, key=lambda e: e.start_ns))]
    return statistics.median(diffs), max(diffs) - min(diffs)


def batch_executions(executions, reps):
    """``[(first, waited), ...]`` a traced batch: the index in
    ``executions`` (the key of the program each device execution ran, in
    order) of the batch's first program and of its last multistep, which
    its sync waits for.  ``reps``: the calls of each batch; the
    multistep's key is ``executions[0]``."""
    multis = [i for i, key in enumerate(executions) if key == executions[0]]
    out, call = [], 0
    for n in reps:
        if call + n > len(multis):
            return _say(f"the trace holds {len(multis)} calls, the batches ran more")
        out.append((multis[call], multis[call + n - 1]))
        call += n
    return out


def bracket(trace, plane, batches):
    """Hop two: ``(lower_ns, upper_ns)``, the bounds on what to add to a
    time on ``plane``'s clock for the profiler's host clock, from the
    harness's spans round the batches ``batches`` (``batch_executions``)."""
    modules = sorted(trace.modules.get(plane, ()), key=lambda m: m.start_ns)
    enqueues = sorted(trace.host.get(ENQUEUE, ()), key=lambda e: e.start_ns)
    syncs = sorted(trace.host.get(SYNC, ()), key=lambda e: e.start_ns)
    if not (len(batches) == len(enqueues) == len(syncs)) or not batches:
        return _say(f"{len(batches)} batches, {len(enqueues)} enqueue spans, "
                    f"{len(syncs)} sync spans")
    if batches[-1][1] >= len(modules):
        return _say(f"{plane} executed {len(modules)} programs, the batches more")
    lower = max(e.start_ns - modules[first].start_ns
                for e, (first, _) in zip(enqueues, batches))
    upper = min(s.end_ns - modules[waited].end_ns
                for s, (_, waited) in zip(syncs, batches))
    return lower, upper


# -- a thread's spans as a timeline -------------------------------------


def innermost(spans):
    """``[(start_ns, end_ns, span), ...]``, disjoint and ascending: the
    spans of one thread (nested, as ``with`` leaves them) cut so that
    every moment belongs to the innermost span open then.  A span's
    pieces add up to its self time."""
    out, stack, at = [], [], float("-inf")

    def own(until):  # the innermost open span owns what is left up to `until`
        nonlocal at
        if stack and until > at:
            out.append((at, until, stack[-1]))
        at = max(at, until)

    for s in sorted(spans, key=lambda s: (s.start_ns, -s.end_ns)):
        while stack and stack[-1].end_ns <= s.start_ns:
            own(stack[-1].end_ns)
            stack.pop()
        own(s.start_ns)
        stack.append(s)
    while stack:
        own(stack[-1].end_ns)
        stack.pop()
    return out


def label(span):
    """A span's name, a ``job/enqueue``'s with the program it enqueued."""
    program = span.counts.get("program")
    return f"{span.name} {program}" if program else span.name


def self_times(spans, start_ns=None, end_ns=None):
    """``{(thread, label): [self seconds, count]}`` over the spans that
    start in ``[start_ns, end_ns]`` (all of them where not given)."""
    by_thread = collections.defaultdict(list)
    for s in spans:
        by_thread[s.thread].append(s)
    out = collections.defaultdict(lambda: [0.0, 0])
    for thread, mine in by_thread.items():
        inside = {s.id for s in mine
                  if (start_ns is None or s.start_ns >= start_ns)
                  and (end_ns is None or s.start_ns <= end_ns)}
        for s in mine:
            if s.id in inside:
                out[thread, label(s)][1] += 1
        for a, b, s in innermost(mine):
            if s.id in inside:
                out[thread, label(s)][0] += (b - a) / 1e9
    return dict(out)


def main_thread(spans):
    """The thread the job's loop runs on."""
    return next((s.thread for s in spans if s.name == "job/advance"), None)


# -- the window's idle, by name -----------------------------------------


@dataclass
class Stretch:
    start_ns: float  # on the profiler's host clock
    end_ns: float
    where: str  # head, gap or tail
    names: dict = field(default_factory=dict)  # name -> ns
    span: object = None  # the job's span that covers most of it

    @property
    def ns(self):
        return self.end_ns - self.start_ns


def idle_stretches(ops, shift_ns, w0, w1):
    """The stretches of ``[w0, w1]`` (host clock) in which none of
    ``ops`` (device clock, ``shift_ns`` behind the host's) ran, and the
    ns of operations that lie outside it."""
    out, at, outside = [], w0, 0.0
    where = "head"
    for e in sorted(ops, key=lambda e: e.start_ns):
        a, b = e.start_ns + shift_ns, e.end_ns + shift_ns
        outside += max(0.0, min(b, w0) - a) + max(0.0, b - max(a, w1))
        a, b = max(a, w0), min(b, w1)
        if b <= a:
            continue
        if a > at:
            out.append(Stretch(at, a, where))
        at, where = max(at, b), "gap"
    if w1 > at:
        out.append(Stretch(at, w1, "tail" if where == "gap" else "head"))
    return out, outside


def name_stretches(stretches, width_ns, segments, syncs):
    """Give every stretch's ns to names: ``below_resolution`` as a whole
    where it is shorter than ``width_ns``; else moment by moment the
    job's span of ``segments`` (``innermost``, on the host clock), else
    ``sync`` inside one of the harness's ``syncs``, else ``no_span``."""
    syncs = sorted(syncs, key=lambda e: e.start_ns)
    for st in stretches:
        if st.ns < width_ns:
            st.names[BELOW] = st.ns
            continue
        covered, best = 0.0, 0.0
        for a, b, span in segments:
            overlap = min(b, st.end_ns) - max(a, st.start_ns)
            if overlap > 0:
                st.names[label(span)] = st.names.get(label(span), 0.0) + overlap
                covered += overlap
                if overlap > best:
                    best, st.span = overlap, span
        in_sync = 0.0
        for e in syncs:
            lo, hi = max(e.start_ns, st.start_ns), min(e.end_ns, st.end_ns)
            if hi > lo:  # less what the job's spans hold of it
                in_sync += hi - lo - sum(
                    max(0.0, min(b, hi) - max(a, lo)) for a, b, _ in segments)
        if in_sync > 0:
            st.names[SYNC] = in_sync
        rest = st.ns - covered - in_sync
        if rest > 0:
            st.names[NO_SPAN] = rest
    return stretches


@dataclass
class Split:
    """The traced window's idle by name, and how far to trust it."""

    host_offset_ns: float
    host_offset_range_ns: float
    lower_ns: float
    upper_ns: float
    window_start_ns: float
    window_ns: float
    idle_ns: float  # window - busy: what `device_idle_share.sw` reads
    outside_ns: float  # device work the trace holds outside the window
    stretches: list

    @property
    def width_ns(self):
        return self.upper_ns - self.lower_ns

    def seconds(self):
        """``{name: [seconds, stretches]}``."""
        out = collections.defaultdict(lambda: [0.0, 0])
        for st in self.stretches:
            for name, ns in st.names.items():
                out[name][0] += ns / 1e9
                out[name][1] += 1
        return dict(out)

    def share(self, kind):
        """``in_sync``, ``in_job`` or ``unnamed`` (the rest of
        ``idle_ns``), in per cent of the window."""
        by = self.seconds()
        in_sync = by.get(SYNC, [0.0])[0] * 1e9
        in_job = sum(v[0] for k, v in by.items()
                     if k not in (SYNC, BELOW, NO_SPAN)) * 1e9
        ns = {"in_sync": in_sync, "in_job": in_job,
              "unnamed": self.idle_ns - in_sync - in_job}[kind]
        return 100.0 * ns / self.window_ns


def split(view):
    """The :class:`Split` of this run's traced window, made once however
    many readers ask and printed then; ``None`` where it cannot be made."""
    if "hostspans_split" not in vars(view):
        found = vars(view)["hostspans_split"] = _split(view)
        if found is not None:
            _print_split(found)
            _print_self_times(view, job_spans(view))
    return vars(view)["hostspans_split"]


def _split(view):
    spans = job_spans(view)
    if spans is None:
        return None
    session, trace = view.session, view.trace
    if not trace.device_ops or not trace.host.get(tracing.WINDOW_SPAN):
        return _say("the trace has no device plane or no window span")
    hop_one = host_offset(view.traced, trace.host.get(SYNC, ()))
    if hop_one is None:
        return None
    whole, executions = session.traced_programs(trace, view.traced)
    if scopes.by_execution(whole, executions) is None:
        return None
    batches = batch_executions(
        executions, [session.rows[s.row]["reps"] for s in view.traced])
    plane = sorted(trace.device_ops)[0]
    bounds = batches and bracket(whole, plane, batches)
    if not bounds:
        return None
    lower, upper = bounds
    if upper < lower:
        return _say(f"the bracket is empty: {lower:.0f} ns > {upper:.0f} ns")
    window = trace.host[tracing.WINDOW_SPAN][0]
    stretches, outside = idle_stretches(
        trace.device_ops[plane], (lower + upper) / 2, window.start_ns, window.end_ns)
    main = main_thread(spans)
    segments = [(a + hop_one[0], b + hop_one[0], s)
                for a, b, s in innermost([s for s in spans if s.thread == main])]
    name_stretches(
        stretches, upper - lower,
        [seg for seg in segments if seg[1] > window.start_ns and seg[0] < window.end_ns],
        trace.host.get(SYNC, ()))
    window_ns = tracing.window_s(trace) * 1e9
    return Split(*hop_one, lower, upper, window.start_ns, window_ns,
                 window_ns - tracing.busy_s(trace) * 1e9, outside, stretches)


def _print_split(found):
    def say(text):
        print(f"perfbench: hostspans: {text}", flush=True)

    say(f"the job's clock + {found.host_offset_ns:.0f} ns is the profiler's host "
        f"clock (range over the traced batches {found.host_offset_range_ns:.0f} ns); "
        f"the device's clock + [{found.lower_ns:.0f}, {found.upper_ns:.0f}] ns is "
        f"the host's: a bracket {found.width_ns / 1e3:.1f} us wide, its middle used")
    say(f"idle {found.idle_ns / 1e9:.6f} s of a window of "
        f"{found.window_ns / 1e9:.6f} s; {found.outside_ns / 1e9:.6f} s of device "
        "work lies outside the window and is counted busy")
    say("idle by what the host's main thread was in: name | s | % of window | stretches")
    for name, (seconds, count) in sorted(
            found.seconds().items(), key=lambda kv: -kv[1][0]):
        say(f"  {name} | {seconds:.6f} | {100e9 * seconds / found.window_ns:.4f} | {count}")
    say("the longest idle stretches: where | us | from the window's start, ms | "
        "names | the job's span, its key")
    for st in sorted(found.stretches, key=lambda st: -st.ns)[:10]:
        names = ", ".join(f"{k} {v / 1e3:.1f}" for k, v in sorted(
            st.names.items(), key=lambda kv: -kv[1]))
        held = f"{label(st.span)}, {st.span.key}" if st.span else "-"
        start = (st.start_ns - found.window_start_ns) / 1e6
        say(f"  {st.where} | {st.ns / 1e3:.1f} | {start:.3f} | {names} | {held}")


def _ends_ns(batches):
    """``(start, end)`` of ``batches`` on the job's clock."""
    return (min(b.start for b in batches) * 1e9, max(b.end for b in batches) * 1e9)


def _print_self_times(view, spans):
    tables = [("the traced window", self_times(spans, *_ends_ns(view.traced))),
              ("the whole run", self_times(spans))]
    print("perfbench: hostspans: self time by thread and span: thread | span | "
          + " | ".join(f"s, count over {title}" for title, _ in tables), flush=True)
    for at in sorted(tables[1][1], key=lambda at: -tables[1][1][at][0]):
        cells = " | ".join("{:.6f}, {}".format(*table.get(at, (0.0, 0)))
                           for _, table in tables)
        print(f"perfbench: hostspans:   {at[0]} | {at[1]} | {cells}", flush=True)


# -- the host's own numbers ---------------------------------------------


def issue_us_per_call(view):
    """What a call costs the loop on the host, in us: the self time of
    ``job/advance``, of the multistep's and the snapshot's ``job/enqueue``
    and of ``job/ask`` on the main thread, over the calls of the window's
    batches, traced or not.  Fetches, callbacks and saves are left out."""
    spans = job_spans(view)
    if spans is None or not view.samples + view.traced:
        return None
    start, end = _ends_ns(view.samples + view.traced)
    main = main_thread(spans)
    mine = [s for s in spans if s.thread == main and start <= s.start_ns <= end]
    calls = sum(s.name == "job/enqueue" and s.counts.get("program") == "multi"
                for s in mine)
    if not calls:
        return _say("the window's batches hold no call of the job")
    issue = sum(b - a for a, b, s in innermost(mine) if s.name in ISSUE
                and s.counts.get("program") != "stage")
    return issue / 1e3 / calls


def save_busy_share(view, name):
    """The median over the saves started inside the window of the union
    of a save's ``name`` spans (``checkpoint/fetch`` or ``/write``, on
    whatever threads) over its ``checkpoint/save`` span, in per cent."""
    spans = job_spans(view)
    if spans is None or not view.samples + view.traced:
        return None
    start, end = _ends_ns(view.samples + view.traced)
    saves = [s for s in spans if s.name == SAVE and start <= s.start_ns <= end]
    if not saves:
        return _say("no save was started and committed inside the window")
    shares = []
    for save in saves:
        mine = [s for s in spans if s.name == name and s.key == save.key
                and save.start_ns <= s.start_ns <= save.end_ns]
        covered = tracing.union_ns(
            [tracing.Event(s.name, s.start_ns, s.end_ns - s.start_ns) for s in mine])
        shares.append(100.0 * covered / (save.end_ns - save.start_ns))
        print(f"perfbench: hostspans: the save of step {save.key}: {len(mine)} "
              f"{name} spans cover {shares[-1]:.2f} % of its "
              f"{save.seconds:.3f} s", flush=True)
    return statistics.median(shares)


def print_long_batches(view):
    """For every batch of the window longer than the median of the
    batches without a save by ``LONG_BATCH_S`` or more: the spans of
    every thread that overlap it, runs of one name on one thread merged
    into a line that names the longest of them."""
    spans = job_spans(view)
    batches = sorted(view.samples + view.traced, key=lambda b: b.start)
    if spans is None or not batches:
        return
    saves = [s for s in spans if s.name == "job/save"]
    plain = [b.seconds for b in batches
             if not any(b.start * 1e9 <= s.start_ns <= b.end * 1e9 for s in saves)]
    if not plain:
        return
    typical = statistics.median(plain)
    long = [(i, b) for i, b in enumerate(batches)
            if b.seconds >= typical + LONG_BATCH_S]
    print(f"perfbench: hostspans: {len(long)} of {len(batches)} batches are "
          f"{1e3 * LONG_BATCH_S:.0f} ms or more over the {1e3 * typical:.3f} ms of "
          f"a batch without a save", flush=True)
    for i, b in long:
        t0, t1 = b.start * 1e9, b.end * 1e9
        print(f"perfbench: hostspans: batch {i}, {1e3 * b.seconds:.3f} ms "
              f"(+{1e3 * (b.seconds - typical):.3f}): thread | span | spans | key "
              "| bytes | from, ms | to, ms | the longest: ms at ms", flush=True)
        over = sorted((s for s in spans if s.end_ns > t0 and s.start_ns < t1),
                      key=lambda s: (s.thread, s.start_ns))
        run = []
        for s in over + [None]:
            if run and (s is None or (s.thread, label(s)) != (run[0].thread, label(run[0]))):
                top = max(run, key=lambda s: s.end_ns - s.start_ns)
                keys = sorted({str(r.key) for r in run})
                print("perfbench: hostspans:   "
                      f"{run[0].thread} | {label(run[0])} | {len(run)} | "
                      f"{keys[0]}{'..' + keys[-1] if len(keys) > 1 else ''} | "
                      f"{sum(r.counts.get('bytes', 0) for r in run)} | "
                      f"{(run[0].start_ns - t0) / 1e6:.3f} | "
                      f"{(run[-1].end_ns - t0) / 1e6:.3f} | "
                      f"{(top.end_ns - top.start_ns) / 1e6:.3f} at "
                      f"{(top.start_ns - t0) / 1e6:.3f}", flush=True)
                run = []
            if s is not None:
                run.append(s)
