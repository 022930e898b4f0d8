"""What a cell's set-up after the chips is made of: the process's build
spans cut into self time and held against ``view.setup["after_chips_s"]``.

The program records every trace of a jitted function, every lowering
and every compile or load that jax reports, and its own imports, as
spans of one recorder of the process (``mpi4jax_tpu/utils/spans.py``
``builds``: ``build/trace``, ``build/lower``, ``build/compile``,
``build/import``) on ``time.perf_counter_ns()``, the clock ``run.py``
takes every batch's start on.  The four readers of
``README.setup-spans.md`` take the spans of the thread the batches ran
on that ended before the window's first batch began.  Traces nest (the
trace of a multistep holds the traces of what it calls, and an import),
so every moment is given to the innermost span open then
(``hostspans.innermost``), never a plain sum; what no span owns is the
driver's own: data, warm-up batches, saves and resumes.

``run.py`` builds nothing before it loads the driver, so nothing is cut
at the front; the first build's start is printed against the first
batch less ``after_chips_s`` for a reader of the log to see that hold.
On a tree without the recorder (the parent of the PR that brought it),
with spans dropped, or with no batch, every function here returns
``None`` and says why; none raises for that.
"""

import collections
import threading
from dataclasses import dataclass

from perfbench.harness import hostspans

TRACE, LOWER, COMPILE, IMPORT = (
    "build/trace", "build/lower", "build/compile", "build/import")
PHASES = (TRACE, LOWER, COMPILE, IMPORT)
ROWS = 40  # of the table; the rest is one line
# A span recorded after the fact begins "its seconds before the callback":
# a child may so begin this long before its parent and still be its child.
SLACK_NS = 100_000


def _say(why):
    print(f"perfbench: setupspans: {why}; nothing is reported", flush=True)


@dataclass
class Piece:
    """A span as it is cut: ``start_ns`` may be moved up to its parent's."""

    start_ns: int
    end_ns: int
    span: object


@dataclass
class Split:
    after_chips_s: float
    self_s: dict  # phase -> seconds of self time, the batches' thread
    rows: list  # ((program, phase), count, self seconds), most expensive first
    counted: int  # spans behind `rows`
    cached: int  # executables the persistent cache served, on any thread
    first_ns: int  # the start of the first of them
    window_ns: int  # the start of the window's first batch
    elsewhere: list  # spans of other threads that ended before the window
    inside: list  # spans of any thread that began inside the window
    astride: list  # began before the window's first batch, ended after it began

    @property
    def unnamed_s(self):
        return self.after_chips_s - sum(self.self_s.values())


def build_spans():
    """The finished spans of the process's recorder of builds, in the
    order they ended, or ``None`` where the program keeps none or has
    dropped some."""
    try:
        from mpi4jax_tpu.utils import spans
    except ImportError:
        return _say("the program has no utils.spans")
    builds = getattr(spans, "builds", None)
    if builds is None:
        return _say("the program keeps no spans of what it builds")
    if builds.dropped:
        return _say(f"the recorder of builds dropped {builds.dropped} spans")
    return builds.spans()


def nested(spans):
    """``Piece``s of one thread's spans, given in the order they ended,
    with every span inside the spans that ended after it and began no
    more than ``SLACK_NS`` before it.  A span recorded after the fact
    begins "its seconds before the callback", microseconds off (jax
    times on ``time.time()``, the recorder on ``perf_counter_ns()``):
    where that puts a child's start before its parent's, the child's is
    moved up; where it puts a span's start before the end of the one
    before it, that one stays its sibling and keeps its seconds."""
    done = []  # (start_ns, pieces of a span and of all inside it), by end
    for span in spans:
        mine = [Piece(span.start_ns, span.end_ns, span)]
        while done and done[-1][0] >= span.start_ns - SLACK_NS:
            for piece in done.pop()[1]:
                piece.start_ns = max(piece.start_ns, span.start_ns)
                mine.append(piece)
        done.append((span.start_ns, mine))
    return [piece for _, pieces in done for piece in pieces]


def self_seconds(spans):
    """``{id(span): seconds}``: one thread's spans' self time."""
    out = collections.Counter()
    for start, end, piece in hostspans.innermost(nested(spans)):
        out[id(piece.span)] += (end - start) / 1e9
    return out


def label(span):
    return span.counts.get("program") or span.counts.get("module") or "?"


def window(view):
    """``(first_ns, last_ns)``: the first batch's start and the last
    batch's end, traced or not, on the recorder's clock."""
    batches = list(view.traced) + list(view.samples)
    if not batches:
        return _say("the window holds no batch")
    return (round(min(b.start for b in batches) * 1e9),
            round(max(b.end for b in batches) * 1e9))


def split(view, spans=None, thread=None):
    """The :class:`Split` of this view's set-up, made once a view and
    printed then; ``None`` with the reason where there is none.
    ``spans`` and ``thread`` default to the process's recorder and the
    thread this is called on, which is the one the batches ran on."""
    if "_setup_split" not in vars(view):
        view._setup_split = _split(view, spans, thread)
        if view._setup_split is not None:
            print_split(view._setup_split)
    return view._setup_split


def _split(view, spans, thread):
    spans = build_spans() if spans is None else spans
    edges = window(view) if spans is not None else None
    if edges is None:
        return None
    first, last = edges
    thread = thread or threading.current_thread().name
    before = [s for s in spans if s.end_ns <= first]
    mine = [s for s in before if s.thread == thread]
    own = self_seconds(mine)
    self_s = dict.fromkeys(PHASES, 0.0)
    rows = collections.defaultdict(lambda: [0, 0.0])
    for s in mine:
        if s.name in self_s:
            self_s[s.name] += own[id(s)]
            row = rows[label(s), s.name]
            row[0] += 1
            row[1] += own[id(s)]
    return Split(
        after_chips_s=view.setup["after_chips_s"], self_s=self_s,
        rows=sorted(((k, n, s) for k, (n, s) in rows.items()),
                    key=lambda row: -row[2]),
        counted=len(mine), cached=sum(bool(s.counts.get("cached")) for s in before),
        first_ns=min((s.start_ns for s in mine), default=first),
        window_ns=first,
        elsewhere=[s for s in before if s.thread != thread],
        inside=[s for s in spans if first <= s.start_ns <= last],
        astride=[s for s in spans if s.start_ns < first < s.end_ns])


def _line(span, origin_ns):
    return (f"perfbench:   {span.thread} | {span.name} | {label(span)} | "
            f"{span.seconds:.6f} s at {(span.start_ns - origin_ns) / 1e9:.3f}")


def print_split(found):
    """The count ``ROADMAP.md`` D19 asked for: a row a (program, phase)
    with its count and self seconds, most expensive first; then what the
    split leaves out, and what was built where nothing may be."""
    first = found.window_ns
    print(f"perfbench: setupspans: {found.counted} build spans on the batches' "
          f"thread before the window ({found.cached} executables from the cache): "
          "program | phase | count | self s", flush=True)
    for (program, phase), count, seconds in found.rows[:ROWS]:
        print(f"perfbench:   {program} | {phase} | {count} | {seconds:.6f}", flush=True)
    rest = found.rows[ROWS:]
    if rest:
        print(f"perfbench:   {len(rest)} more rows | | {sum(r[1] for r in rest)} | "
              f"{sum(r[2] for r in rest):.6f}", flush=True)
    named = " ".join(f"{phase} {found.self_s[phase]:.6f}" for phase in PHASES)
    print(f"perfbench: setupspans: of after_chips_s {found.after_chips_s:.6f}: "
          f"{named} unnamed {found.unnamed_s:.6f}", flush=True)
    begin = first - round(found.after_chips_s * 1e9)
    print(f"perfbench: setupspans: the first build began "
          f"{(found.first_ns - begin) / 1e9:+.3f} s from the first batch less "
          "after_chips_s (a traced run's profiler starts between the two)",
          flush=True)
    if found.elsewhere:
        print(f"perfbench: setupspans: {len(found.elsewhere)} builds on other "
              "threads before the window, not counted:", flush=True)
        for s in found.elsewhere[:ROWS]:
            print(_line(s, begin), flush=True)
    late = found.astride + found.inside
    print(f"perfbench: setupspans: {len(found.inside)} builds began inside the "
          f"window, {len(found.astride)} lay astride its start"
          + (":" if late else " (limit 0)"), flush=True)
    for s in late[:ROWS]:
        print(_line(s, first), flush=True)


def phase_seconds(view, phase):
    """The self time of ``phase`` in this view's set-up, or ``None``."""
    found = split(view)
    return None if found is None else found.self_s[phase]


def unnamed_seconds(view):
    """What is left of ``after_chips_s`` once the four phases are taken
    out.  Prints ``build/compile``'s self time beside the harness's own
    ``compile_s`` (jax's counter, summed from outside): the same
    quantity twice, and the one check from outside these spans have."""
    found = split(view)
    if found is None:
        return None
    ours, theirs = found.self_s[COMPILE], view.compile["compile_s"]
    print(f"perfbench: setupspans: build/compile's self time {ours:.6f} s, the "
          f"harness's compile_s {theirs:.6f}: {abs(ours - theirs) * 1e3:.3f} ms apart"
          + ("" if abs(ours - theirs) < 0.010 else
             " (MORE THAN 10 ms: a compile on another thread, or under another span)"),
          flush=True)
    return found.unnamed_s
