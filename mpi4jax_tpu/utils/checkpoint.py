"""Checkpoint / resume for sharded state (SURVEY §5.4: the reference has
no checkpointing — its closest analog is gathering the solution to rank
0 for post-processing, examples/shallow_water.py:586-593 there; this
module makes resumable state a first-class subsystem).

Two ways to disk, one directory layout (``<directory>/<step>/``, a save
in progress under another name until it is whole):

* **orbax** (:func:`save`, :func:`restore`, :class:`Manager`): any
  pytree; each device writes its own shards (OCDBT), so saving a
  pod-sharded pytree never funnels the whole state through one host.
  Orbax hands whole arrays to the runtime, which is right for a train
  step's parameters and wrong for a solver's 415 MB fields under a
  host's finite staging buffer (``Snapshot.ahead_bytes`` has the
  numbers).

      ckpt.save(path, {"state": state, "step": step})
      restored = ckpt.restore(path, like={"state": state, "step": step})

  ``like`` supplies shapes/dtypes/shardings (pass the live pytree or one
  built from ``jax.eval_shape``); restored arrays come back with the
  same sharding they were saved from.

* **streamed** (:class:`Series`, :class:`Save`, :func:`to_host`,
  :func:`read_pieces`): arrays that their owner has cut into pieces on
  the device go to the host with a bound on the bytes asked for and not
  yet fetched, are written into one ``.npy`` file an array by background
  threads beside whatever the caller runs next, and are committed by one
  rename; a restore reads them back under the same bound.  What
  ``models.shallow_water``'s job saves and resumes through.  Where the
  owner has other copies on their way to the same host (a job's
  snapshots), all of them count against one :class:`HostBound`.  Each takes
  a ``trace`` (a :class:`mpi4jax_tpu.utils.spans.Recorder`, its owner's;
  one of its own where none is given) and records what its threads did
  under ``checkpoint/...``: every time it reports is its spans'.
"""

import collections
import contextlib
import functools
import json
import os
import pathlib
import queue
import shutil
import threading

import numpy as np

import jax

from mpi4jax_tpu.utils.spans import Recorder

__all__ = [
    "save", "restore", "latest_step", "Manager",
    "Series", "Save", "piece_rows", "begin_npy", "write_at", "to_host",
    "read_pieces", "HostBound", "Side",
]


@functools.cache
def _checkpointer():
    """The one-shot functions' checkpointer, built when first asked for
    and kept: it holds no path."""
    import orbax.checkpoint as ocp

    return ocp.StandardCheckpointer()


def save(path, tree, *, force=True):
    """Write ``tree`` (any pytree of arrays / scalars) to ``path``.

    Safe for sharded arrays: every process writes only its addressable
    shards.  ``force=True`` overwrites an existing checkpoint.
    """
    ckptr = _checkpointer()
    ckptr.save(pathlib.Path(path).absolute(), tree, force=force)
    ckptr.wait_until_finished()


def restore(path, *, like):
    """Read a pytree written by :func:`save`.

    ``like`` is a pytree matching the saved structure whose leaves
    provide shape/dtype/sharding — pass the live state (its values are
    not read) or abstract leaves from ``jax.eval_shape`` with shardings
    attached.
    """
    return _checkpointer().restore(
        pathlib.Path(path).absolute(), jax.tree.map(_abstractify, like))


def _abstractify(leaf):
    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        sharding = getattr(leaf, "sharding", None)
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=sharding)
    return leaf


def latest_step(directory):
    """Highest step committed in ``directory`` by a :class:`Manager` or a
    :class:`Series`, or None: a directory listing, nothing is opened."""
    return Series(directory).latest()


class Manager:
    """Stepped checkpoint series with retention — resume-after-failure
    for long solver / training runs (the elastic-recovery building block
    the reference lacks, SURVEY §5.3/§5.4).

        with checkpoint.Manager(dir, max_to_keep=3) as mgr:
            start = mgr.latest_step() or 0
            state = mgr.restore(start, like=state) if start else state
            for step in range(start, n):
                state = advance(state)
                mgr.maybe_save(step + 1, state, every=100)
    """

    def __init__(self, directory, *, max_to_keep=3):
        import orbax.checkpoint as ocp

        self._mgr = ocp.CheckpointManager(
            pathlib.Path(directory).absolute(),
            options=ocp.CheckpointManagerOptions(max_to_keep=max_to_keep),
        )

    def latest_step(self):
        return self._mgr.latest_step()

    def save(self, step, tree):
        import orbax.checkpoint as ocp

        self._mgr.save(step, args=ocp.args.StandardSave(tree))

    def maybe_save(self, step, tree, *, every):
        if every and step % every == 0:
            self.save(step, tree)
            return True
        return False

    def restore(self, step, *, like):
        import orbax.checkpoint as ocp

        abstract = jax.tree.map(_abstractify, like)
        return self._mgr.restore(
            step, args=ocp.args.StandardRestore(abstract)
        )

    def wait_until_finished(self):
        """Durability barrier: block until every pending save is
        COMMITTED (the orbax step dir renamed out of its ``.tmp``
        form).  Fault-tolerant loops call this before telling other
        ranks the step is safe — a crash after ``save()`` but before
        commit would otherwise leave only a ``.orbax-checkpoint-tmp``
        dir that ``latest_step()`` ignores on restart."""
        self._mgr.wait_until_finished()

    def close(self):
        self._mgr.wait_until_finished()
        self._mgr.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


# -- streamed saves -----------------------------------------------------

MANIFEST = "manifest.json"
# In the name of whatever is not a committed save: a save on its way, or
# a committed one on its way out.  Orbax's own temporaries are not ours.
TEMPORARY = ".partial-"
# The most of a piece that one device holds, and the most bytes a device
# of a save's pieces asked for and not yet fetched, whatever more the
# caller's host would take.  Measured on a v5e's host beside a running solver
# (PERF.md, PR 36): a device runs its copies to the host and its
# programs through one queue, so a step's launch waits for the copies
# asked for ahead of it, and a save costs the loop what is in flight:
# 58 ms a save at 160e6 bytes of 8 MiB pieces, 33 at 64e6, 20 at 32e6,
# and 12 ms (8 of them the staging program's own) at 16e6 of 4 MiB
# pieces, which still reach the host at 2.5-5 GB/s.  Pieces of 69 MB
# cost 150-300 ms a save whatever the bound (a host buffer over glibc's
# largest mmap threshold, 32 MiB, is mapped and faulted in afresh for
# every piece).  So the bound is the device's queue's before it is a
# host's staging buffer's, and the library holds it for every caller.
PIECE_BYTES = 4 << 20
AHEAD_BYTES = 16_000_000
# A piece is written from a buffer its writer keeps, in calls of this
# size, by this many threads, over the files of a save that has gone
# where there is one (`Series.begin`).  Read on two filesystems
# (PERF.md, PR 36), 2.49 GB a save in 4 MiB pieces.  A local ext4 disk,
# host only: over a save's files 0.36-0.57 s where new files take 0.5 s
# to 9 s (the disk's own pace, once its cache is full); two writers
# through their buffers as fast as two without, one writer slower
# through its buffer (0.55 against 0.38); calls of 256 KiB as fast as
# calls of 4 MiB (0.57, 0.45).  9p under a sandbox, the chip's machine,
# beside a running solver: a freshly fetched buffer goes to a file at
# 1.2 GB/s and one the filesystem has seen before at 2.7-4.4, so two
# writers through their buffers commit a save in 0.9-1.7 s where two
# without took 2.2-2.7 and one 2.7; new files were throttled for
# seconds from a process's fourth save on where files written over
# never were; and a write holds up whatever else the process asks of
# its host while it lasts: beside calls of 4 MiB one or two of the
# loop's batches a run lost 10-40 ms enqueueing, beside calls of
# 256 KiB none did, and calls of 64 KiB commit too late (2.4-2.6 s).
WRITE_BYTES = 256 << 10
WRITERS = 2


class Series:
    """A directory of numbered saves, ``<directory>/<step>/`` each: one
    ``.npy`` file an array and a ``manifest.json``, written last.  A save
    is written under ``<step>.partial-<pid>`` and committed by one
    rename, so a directory named by digits alone is whole; whatever an
    interrupted save left is never taken for one, and :meth:`clean`
    removes it.  ``keep``: the newest saves left standing (``None``:
    all).  A save beyond them goes only once the new one is committed,
    so from the ``keep``-th commit on ``keep`` saves stand committed at
    every moment; the newest of those that go is kept under a temporary
    name as the next save's files (:meth:`prune`, :meth:`begin`), so a
    series holds ``keep + 1`` saves' bytes, as it would while a save is
    written, and from then on no save makes or removes a file.  Durable
    against the death of the process, not of the machine: nothing is
    ``fsync``ed."""

    def __init__(self, directory, *, keep=None):
        self.directory = pathlib.Path(directory).absolute()
        self.keep = keep

    def steps(self):
        """The committed steps, ascending."""
        if not self.directory.is_dir():
            return []
        return sorted(int(p.name) for p in self.directory.iterdir()
                      if p.name.isdigit() and p.is_dir())

    def latest(self):
        steps = self.steps()
        return steps[-1] if steps else None

    def path(self, step):
        return self.directory / str(step)

    def leftovers(self):
        return sorted(self.directory.glob(f"*{TEMPORARY}*"))

    def clean(self):
        """Remove what an interrupted save left behind, and the spare
        files kept for the next one; returns how many there were.  For
        the one process that writes the directory, before it writes or
        when it is done."""
        left = self.leftovers()
        for path in left:
            shutil.rmtree(path, ignore_errors=True)
        return len(left)

    def begin(self, step):
        """The directory a save of ``step`` is written in: the spare one
        that :meth:`prune` left, files and all, for the new save to
        write over, or a new one."""
        tmp = self.directory / f"{step}{TEMPORARY}{os.getpid()}"
        shutil.rmtree(tmp, ignore_errors=True)
        if self._spare().is_dir():
            os.rename(self._spare(), tmp)
            (tmp / MANIFEST).unlink(missing_ok=True)
        else:
            tmp.mkdir(parents=True)
        return tmp

    def commit(self, tmp, step):
        """``tmp`` becomes the save of ``step``, in the place of an
        older one of that step."""
        final = self.path(step)
        if final.exists():
            self._retire(final)
        os.rename(tmp, final)

    def prune(self):
        """The saves beyond the newest ``keep`` go: out of the committed
        names first (a removal cut short leaves a leftover, not half a
        save), the newest of them to be the next save's files."""
        if self.keep:
            for old in self.steps()[:-self.keep]:
                self._retire(self.path(old))

    def _spare(self):
        return self.directory / f"spare{TEMPORARY}{os.getpid()}"

    def _retire(self, path):
        shutil.rmtree(self._spare(), ignore_errors=True)
        os.rename(path, self._spare())

    def manifest(self, step):
        path = self.path(step) / MANIFEST
        if not path.is_file():
            raise ValueError(
                f"{self.path(step)} holds no {MANIFEST}: not a streamed "
                "save of this module (one of orbax's is read by `restore` "
                "or `Manager.restore`)")
        return json.loads(path.read_text())


def piece_rows(rows, row_bytes, piece_bytes=PIECE_BYTES):
    """``[(lo, hi), ...]``: ``rows`` rows of ``row_bytes`` each cut into
    the fewest bands of at most ``piece_bytes``, as even as they come
    (a row wider than that is a band of its own)."""
    most = max(int(piece_bytes // row_bytes), 1)
    n = -(-rows // most)
    edges = [round(i * rows / n) for i in range(n + 1)]
    return list(zip(edges, edges[1:]))


def begin_npy(path, shape, dtype):
    """Make ``path`` the ``.npy`` file of an array of ``shape`` and
    ``dtype`` whose rows are still to come, over a file that is there
    (its bytes stay where the new array's will lie) or anew; returns
    ``(fd, where its first row goes)``."""
    fd = os.open(path, os.O_WRONLY | os.O_CREAT, 0o644)
    with os.fdopen(os.dup(fd), "wb") as f:
        np.lib.format.write_array_header_1_0(f, {
            "descr": np.lib.format.dtype_to_descr(np.dtype(dtype)),
            "fortran_order": False, "shape": tuple(shape)})
        start = f.tell()
    os.ftruncate(fd, start + int(np.prod(shape)) * np.dtype(dtype).itemsize)
    return fd, start


def write_at(fd, offset, array, bounce):
    """``array``'s bytes into ``fd`` at ``offset``, in calls of
    ``bounce``'s size, each from ``bounce`` (a ``uint8`` buffer the
    caller keeps).  Positional, so threads may share ``fd``."""
    data = np.ascontiguousarray(array).reshape(-1).view(np.uint8)
    for at in range(0, data.size, bounce.size):
        n = min(bounce.size, data.size - at)
        np.copyto(bounce[:n], data[at:at + n])
        os.pwrite(fd, memoryview(bounce)[:n], offset + at)


# The kinds of copies to the host that one :class:`HostBound` counts.
SNAPSHOT, SAVE = "snapshot", "save"


class HostBound:
    """The bytes of an owner's copies to the host that are asked for and
    not yet fetched, of every kind and from every thread, under one
    figure: ``most``, what the host takes (``None``: whatever is asked).
    A job's snapshots, asked for and fetched by its loop, and the pieces
    of its save, asked for and fetched by the save's thread, go through
    one staging buffer and one device queue, so they count together.

    :meth:`take` makes room for a copy of ``size`` bytes of ``kind``
    before it is asked for and :meth:`give` returns it once the copy is
    fetched.  A copy fits while what is in flight and it stay under
    ``most``.  One that alone is over ``most`` goes alone, with nothing
    else of either kind in flight: the one case in which ``peak``, the
    most bytes in flight since it was last reset, reads over ``most``,
    by that copy's size.

    First come, first served, and neither kind starves: a thread that
    can do nothing but wait (``wait=True``: none of its own copies is in
    flight, so the room is the other kind's) is served before any copy
    asked for later, and the other kind makes that room by fetching what
    it has, which needs none.  Nothing is set aside for either kind
    (PERF.md, PR 45, has the form that did, measured beside this one)."""

    def __init__(self, most=None):
        self.most = most
        self.peak = 0
        self._held = collections.Counter()
        self._first = None  # the kind that waits for room: none goes ahead of it
        self._changed = threading.Condition()

    @property
    def in_flight(self):
        return sum(self._held.values())

    def holder(self, but):
        """The kind other than ``but`` that holds a copy of ``but`` back:
        the one with the most bytes in flight, else the one whose wait
        comes first."""
        with self._changed:
            held = {k: n for k, n in self._held.items() if k != but and n}
            return max(held, key=held.get) if held else self._first

    def take(self, kind, size, wait=False):
        """Count ``size`` bytes of ``kind`` as in flight if they fit;
        ``wait``: block until they do.  Returns whether they were."""
        with self._changed:
            while not self._fits(kind, size):
                if not wait:
                    return False
                if self._first is None:  # whoever waits in `until` hears of it
                    self._first = kind
                    self._changed.notify_all()
                self._changed.wait()
            if self._first == kind:
                self._first = None
            self._held[kind] += size
            self.peak = max(self.peak, self.in_flight)
            self._changed.notify_all()
            return True

    def give(self, kind, size):
        with self._changed:
            self._held[kind] -= size
            self._changed.notify_all()

    def until(self, done, kind):
        """Block until ``done()`` or until a copy of another kind waits
        for room that ``kind``'s copies hold; returns ``done()``.
        Whoever makes ``done()`` true calls :meth:`wake`."""
        with self._changed:
            while not done() and not (
                    self._first not in (None, kind) and self._held[kind]):
                self._changed.wait()
            return done()

    def wake(self):
        with self._changed:
            self._changed.notify_all()

    def _fits(self, kind, size):
        if self._first not in (None, kind):
            return False
        return (self.most is None or not self.in_flight
                or self.in_flight + size <= self.most)


class Side:
    """One kind of copies under a :class:`HostBound`, for the one thread
    that asks for them and fetches them.  ``span(**counts)`` makes the
    span a wait is recorded under (``held_by``: the kind whose bytes
    held the copy back; ``bytes``: the copy's); ``waited_s`` is the sum
    of those spans."""

    def __init__(self, bound, kind, span):
        self.bound, self.kind, self.span = bound, kind, span
        self.held, self.waited_s = 0, 0.0

    def take(self, size, wait=False, **counts):
        """Room for a copy of ``size`` bytes; ``wait``: block, under a
        span, until there is.  Returns whether there is."""
        if not self.bound.take(self.kind, size):
            if not wait:
                return False
            with self.span(held_by=self.bound.holder(self.kind), bytes=size,
                           **counts) as waited:
                self.bound.take(self.kind, size, wait=True)
            self.waited_s += waited.seconds
        self.held += size
        return True

    def give(self, size):
        self.held -= size
        self.bound.give(self.kind, size)

    def close(self):
        """Return what is still held (copies that were never fetched)."""
        self.give(self.held)


def to_host(pieces, ahead_bytes=None, span=None, side=None):
    """``(key, device array)`` pairs → ``(key, numpy array)`` pairs,
    in order.  The copies to the host are asked for ahead of the fetch,
    oldest first, as far as ``ahead_bytes`` goes: at most that many
    bytes asked for and not yet fetched, the oldest piece's always
    (``None``: all of them at once).  Takes ``pieces`` (a list) apart as
    it goes, so that each device array is released once it is fetched.
    ``span(piece)``, where given, is entered round each fetch (a
    save's ``checkpoint/fetch``).  ``side`` (a :class:`Side`), where
    given, is the bound the pieces share with their owner's other
    copies: a piece is asked for once it fits there too, and the oldest
    is waited for where nothing of these is in flight."""
    waiting = collections.deque(pieces)
    del pieces[:]
    asked = asked_bytes = 0
    while waiting:
        while asked < len(waiting):
            size = waiting[asked][1].nbytes
            if asked and ahead_bytes is not None and asked_bytes + size > ahead_bytes:
                break
            if side is not None and not side.take(size, wait=not asked):
                break
            waiting[asked][1].copy_to_host_async()
            asked += 1
            asked_bytes += size
        name, piece = waiting.popleft()
        with span(piece) if span else contextlib.nullcontext():
            host = np.asarray(piece)
        asked -= 1
        asked_bytes -= piece.nbytes
        if side is not None:
            side.give(piece.nbytes)
        del piece
        yield name, host


class Save:
    """One streamed save on its way.  ``files``: ``{file name: (shape,
    dtype)}``, the arrays as they will lie on disk; ``pieces``:
    ``((file name, first row), device array)`` pairs, bands of rows of
    those arrays, which the save takes over.  The pieces go to the host
    under ``ahead_bytes`` on one thread and into their files on
    ``WRITERS`` others, then ``manifest`` is written and the save
    committed; ``on_commit(record)`` is then called from the first
    thread, ``record`` holding ``step``, ``bytes``, ``stage_s`` (start
    to the last piece on the host), ``commit_s`` (start to the rename)
    and ``fetch_wait_s`` (below).  :meth:`wait` blocks until the save is
    committed and the series pruned, and raises what stopped it.

    ``bound`` (a :class:`HostBound`, its owner's): the pieces' copies
    count against it beside the owner's other copies to the host.  A
    piece that other copies hold back, none of the save's own being in
    flight, is waited for under ``checkpoint/fetch_wait`` (``held_by``,
    ``bytes``), and ``fetch_wait_s`` is those spans' sum.  Such a save
    goes on only as its owner fetches those copies: the owner waits for
    it through :meth:`HostBound.until`, not :meth:`wait` alone.

    The spans of ``trace``, all under the key ``step``: on the first
    thread (``checkpoint-save``) ``checkpoint/save`` from its start to
    the rename (``cause``: the span of the caller that handed the save
    over), inside it ``checkpoint/fetch`` a piece, ``checkpoint/close``
    (the files' descriptors, once the writers have ended) and
    ``checkpoint/commit``, which is ``checkpoint/manifest`` (the
    manifest written) and ``checkpoint/rename`` (:meth:`Series.commit`),
    after it ``checkpoint/prune``; on the writers (``checkpoint-write-<i>``)
    ``checkpoint/write`` a piece, its cause the ``checkpoint/save``.
    ``commit_s`` is the first's length and ``stage_s`` the end of its
    last fetch.

    A file an array, not a file a piece (six files a save, not 606),
    written by position so that the writers share it."""

    def __init__(self, series, step, manifest, files, pieces, *,
                 ahead_bytes=None, on_commit=None, trace=None, cause=None,
                 bound=None):
        self.step = step
        self.bytes = sum(piece.nbytes for _, piece in pieces)
        self.record = None
        self._error = None
        self._trace = trace or Recorder()
        self._fetched = None  # the newest `checkpoint/fetch`
        self._host = queue.Queue()
        self._done = threading.Event()
        self._bound = bound or HostBound()
        self._side = Side(self._bound, SAVE, functools.partial(
            self._trace.span, "checkpoint/fetch_wait", key=step))
        threading.Thread(
            target=self._run, daemon=True, name="checkpoint-save",
            args=(series, manifest, files, pieces, ahead_bytes, on_commit,
                  cause)).start()

    @property
    def committed(self):
        return self.record is not None

    @property
    def done(self):
        """Committed and pruned, or failed: :meth:`wait` returns at once."""
        return self._done.is_set()

    def wait(self):
        self._done.wait()
        if self._error is not None:
            raise RuntimeError(
                f"the save of step {self.step} failed") from self._error
        return self.record

    def _run(self, series, manifest, files, pieces, ahead_bytes, on_commit, cause):
        span, step = self._trace.span, self.step
        try:
            with span("checkpoint/save", key=step, cause=cause,
                      bytes=self.bytes) as whole:
                tmp = series.begin(step)
                self._stream(tmp, files, pieces, ahead_bytes, whole.id)
                if self._error is None:
                    with span("checkpoint/commit", key=step):
                        with span("checkpoint/manifest", key=step):
                            (tmp / MANIFEST).write_text(json.dumps(manifest))
                        with span("checkpoint/rename", key=step):
                            series.commit(tmp, step)
            if self._error is None:
                staged_ns = self._fetched.end_ns if self._fetched else whole.start_ns
                self.record = {
                    "step": step, "bytes": self.bytes,
                    "stage_s": (staged_ns - whole.start_ns) / 1e9,
                    "commit_s": whole.seconds,
                    "fetch_wait_s": self._side.waited_s}
                if on_commit is not None:
                    on_commit(self.record)
                with span("checkpoint/prune", key=step, cause=whole.id):
                    series.prune()
        except BaseException as error:  # handed to whoever waits
            self._error = self._error or error
        finally:
            self._done.set()
            self._bound.wake()

    def _stream(self, tmp, files, pieces, ahead_bytes, cause):
        """The pieces through the host into their files under ``tmp``."""
        opened = {}
        for stale in set(p.name for p in tmp.iterdir()) - set(files):
            (tmp / stale).unlink()
        for name, (shape, dtype) in files.items():
            fd, start = begin_npy(tmp / name, shape, dtype)
            row_bytes = int(np.prod(shape[1:])) * np.dtype(dtype).itemsize
            opened[name] = fd, start, row_bytes
        writers = [threading.Thread(target=self._write, args=(opened, cause),
                                    daemon=True, name=f"checkpoint-write-{i}")
                   for i in range(WRITERS)]
        for writer in writers:
            writer.start()
        try:
            for item in to_host(pieces, ahead_bytes, span=self._fetching,
                                side=self._side):
                self._host.put(item)
        finally:
            self._side.close()
            for writer in writers:
                self._host.put(None)
            for writer in writers:
                writer.join()
            with self._trace.span("checkpoint/close", key=self.step):
                while opened:
                    os.close(opened.popitem()[1][0])

    def _fetching(self, piece):
        self._fetched = self._trace.span(
            "checkpoint/fetch", key=self.step, bytes=piece.nbytes)
        return self._fetched

    def _write(self, opened, cause):
        bounce = np.empty(WRITE_BYTES, np.uint8)
        while (item := self._host.get()) is not None:
            try:
                if self._error is None:
                    (name, row), host = item
                    fd, start, row_bytes = opened[name]
                    with self._trace.span("checkpoint/write", key=self.step,
                                          cause=cause, bytes=host.nbytes):
                        write_at(fd, start + row * row_bytes, host, bounce)
            except BaseException as error:
                self._error = self._error or error


def read_pieces(path, bands, sharding, ahead_bytes=None, trace=None, key=None):
    """The bands of rows ``bands`` (``[(lo, hi), ...]``, ascending) of
    the ``.npy`` file ``path`` as device arrays of ``sharding``, in
    order, with the copies to the device that are not yet done held
    under ``ahead_bytes`` (the newest's always; ``None``: no bound).
    Returns ``(arrays, read_s, to_device_s)``: host seconds reading the
    file, and handing its bands to the device or waiting for it, each
    the sum of ``trace``'s spans ``checkpoint/read`` and
    ``checkpoint/to_device``, one a band, under ``key`` (the step of
    the save that is read)."""
    span = (trace or Recorder()).span
    arrays, flying, flying_bytes = [], collections.deque(), 0
    read_s = to_device_s = 0.0
    with open(path, "rb") as f:
        np.lib.format.read_magic(f)
        shape, _, dtype = np.lib.format.read_array_header_1_0(f)
        start, row = f.tell(), int(np.prod(shape[1:]))
        for lo, hi in bands:
            size = (hi - lo) * row * dtype.itemsize
            with span("checkpoint/read", key=key, bytes=size) as read:
                f.seek(start + lo * row * dtype.itemsize)
                host = np.fromfile(f, dtype, (hi - lo) * row).reshape(
                    (hi - lo,) + tuple(shape[1:]))
            with span("checkpoint/to_device", key=key, bytes=size) as sent:
                while (flying and ahead_bytes is not None
                       and flying_bytes + host.nbytes > ahead_bytes):
                    flying_bytes -= jax.block_until_ready(flying.popleft()).nbytes
                arrays.append(jax.device_put(host, sharding))
                flying.append(arrays[-1])
                flying_bytes += host.nbytes
            read_s += read.seconds
            to_device_s += sent.seconds
    return arrays, read_s, to_device_s
