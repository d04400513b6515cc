"""Host spans on the profiler's clock, kept in a bounded ring in memory.

``span(name, **attrs)`` times one stretch of host work on the calling
thread.  It opens a ``jax.profiler.TraceAnnotation`` of the same name, so a
profiler trace shows the span on the host plane, on the same clock as the
device's events; that is what lets an idle stretch of the device be blamed
on a host stage.  On exit it appends one record to the process-wide ring:
``(name, start_ns, end_ns, span_id, parent_id, root_id, thread, attrs)``.
The parent is the innermost open span of the thread; the root is the
outermost one's id, so every span of one top-level call shares it.  A
worker thread joins its caller's tree with ``attach(context())``.

``record(name, start_ns, end_ns, root=...)`` adds a span that started on
one thread and ended on another; it goes to memory only.  A ``Chain``
records consecutive stages at once, at a fraction of a span's cost, for the
serve path: a request's three stages, or a dispatcher round's, each stage
paired with its ``annotate`` for the trace.

Timestamps are ``time.time_ns()``: CLOCK_REALTIME, the clock the profiler
stamps its host events with (a trace gives them relative to its
``profile_start_time``).  Attribute values are non-negative integers
(rows, bytes, a bucket, a ticket).

The ring holds ``CAPACITY`` rows of 72 bytes in preallocated numeric
columns, with names, thread names and attribute keys interned, so its
memory is fixed; a row holds one span, or one chain of up to ``MAX_CHAIN``
stages, which become records when read.  When full it drops the oldest
rows and counts their records; ``RING.records(root)`` then returns None for
every root that lost one, so a reader never sees part of a tree.  It is
always on; with no trace running, a span skips its annotation.

A span or a chain is staged as one row of integers (a list ``extend``) and
up to ``_STAGE`` rows move into the columns at once, when the stage is full
or the ring is read.  The serve queue runs near its knee, where every
microsecond of the interpreter's time per request shows in its latency.
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import time
from typing import NamedTuple

import numpy as np
from jax.profiler import TraceAnnotation

# rows; a 30 s serve window at 2,400 requests/s writes about 110,000 (one
# chain per request, two per microbatch: 290,000 records)
CAPACITY = 1 << 20
MAX_ATTRS = 3
MAX_CHAIN = 3                  # stages of one chain row

now_ns = time.time_ns
_tracing = TraceAnnotation.is_enabled

# a span is a row of int64: its interned (name, thread, attribute keys),
# start, end, span, parent and root ids, and the attribute values
_ROW = ("meta", "start_ns", "end_ns", "span_id", "parent_id",
        "root_id") + tuple(f"value{i}" for i in range(MAX_ATTRS))
_WIDTH = len(_ROW)
_ROOT = _ROW.index("root_id")
_VALUE = _ROW.index("value0")
_COLUMNS = _ROW[1:_VALUE]
# a chain row holds -1 - its interned chain and its stage boundaries in
# place of start to parent; its stages become records when read, with ids
# of their own
_TIMES = 1
_CHAIN_IDS = 1 << 62
_NO_VALUES = (0,) * MAX_ATTRS
_NO_TIMES = (0,) * MAX_CHAIN
_STAGE = 1024 * _WIDTH


class Context(NamedTuple):
    """Where a span opened now would hang: its parent and its root."""
    span_id: int
    root_id: int


class Ring:
    """A fixed number of rows of span records, oldest dropped first."""

    def __init__(self, capacity: int = CAPACITY):
        self.capacity = capacity
        self.dropped = 0
        self._buf = np.zeros((capacity, _WIDTH), np.int64)
        self._stage: list[int] = []     # whole rows, flat
        self._n = 0                     # rows moved into _buf, ever
        self._lost: set[int] = set()    # roots that lost a record
        self._metas: dict[tuple, int] = {}
        self._spans: dict[tuple, int] = {}  # (name, thread, *keys) -> meta
        self._meta_keys: list[tuple] = []   # (names, thread, keys)
        self._lock = threading.Lock()

    @property
    def nbytes(self) -> int:
        return self._buf.nbytes

    def _meta(self, names: tuple, thread: str, keys: tuple) -> int:
        m = self._metas.get((names, thread, keys))
        if m is not None:
            return m
        if len(keys) > MAX_ATTRS or len(names) > MAX_CHAIN:
            raise ValueError(f"span {names[0]!r}: more than {MAX_ATTRS} "
                             f"attributes {sorted(keys)} or more than "
                             f"{MAX_CHAIN} stages")
        with self._lock:
            # a chain's stages are interned with it, for ``_expand``
            for key in ((names, thread, keys),) + tuple(
                    ((n,), thread, keys) for n in names):
                if key not in self._metas:
                    self._metas[key] = len(self._meta_keys)
                    self._meta_keys.append(key)
        return self._metas[names, thread, keys]

    def append(self, name: str, start_ns: int, end_ns: int, span_id: int,
               parent_id: int, root_id: int, thread: str,
               attrs: dict) -> None:
        m = self._spans.get((name, thread, *attrs))
        if m is None:
            m = self._spans[name, thread, *attrs] = self._meta(
                (name,), thread, tuple(attrs))
        if attrs:
            self.put((m, start_ns, end_ns, span_id, parent_id, root_id,
                      *attrs.values(), *_NO_VALUES[len(attrs):]))
        else:
            self.put((m, start_ns, end_ns, span_id, parent_id, root_id,
                      *_NO_VALUES))

    def append_chain(self, names: tuple, times_ns: tuple, root_id: int,
                     thread: str, attrs: dict) -> None:
        """Records ``names[i]`` from ``times_ns[i]`` to ``times_ns[i + 1]``
        under ``root_id``, in one row."""
        m = self._meta(names, thread, tuple(attrs))
        self.put((-1 - m, *times_ns, *_NO_TIMES[len(names):], root_id,
                  *attrs.values(), *_NO_VALUES[len(attrs):]))

    def put(self, row: tuple) -> None:
        """Stage one row.  No lock: one extend adds a whole row, and a
        flush takes rows from the front in place."""
        stage = self._stage
        stage.extend(row)
        if len(stage) >= _STAGE:
            with self._lock:
                self._flush()

    def _flush(self) -> None:
        """Move the staged rows into the columns (the lock is held)."""
        n_ints = len(self._stage)
        if not n_ints:
            return
        rows = np.array(self._stage[:n_ints], np.int64).reshape(-1, _WIDTH)
        del self._stage[:n_ints]
        k, cap, n0 = len(rows), self.capacity, self._n
        end = n0 + k
        old = np.arange(max(0, n0 - cap), min(n0, end - cap))
        if old.size:                      # stored rows pushed out
            self._lose(self._buf[old % cap])
        if k > cap:                       # the batch alone overflows
            self._lose(rows[:k - cap])
            rows = rows[k - cap:]
        at = (end - len(rows)) % cap
        head = min(len(rows), cap - at)
        self._buf[at:at + head] = rows[:head]
        self._buf[:len(rows) - head] = rows[head:]
        self._n = end

    def _lose(self, rows: np.ndarray) -> None:
        """Count the records of dropped rows and mark their roots
        incomplete."""
        chains = rows[:, 0] < 0
        self.dropped += int(np.sum(~chains)) + sum(
            len(self._meta_keys[-1 - m][0]) for m in rows[chains, 0])
        self._lost.update(np.unique(rows[:, _ROOT]).tolist())

    def _rows(self) -> tuple[np.ndarray, np.ndarray]:
        """The retained rows, oldest first, and their positions."""
        n = min(self._n, self.capacity)
        at = self._n % self.capacity if self._n > self.capacity else 0
        rows = np.concatenate([self._buf[at:n], self._buf[:at]])
        return rows, np.arange(self._n - n, self._n)

    def _expand(self, rows: np.ndarray, pos: np.ndarray) -> np.ndarray:
        """Span rows as they are, and one record per stage of each chain
        row; a chain's stages take ids of their own, from its position."""
        chains = rows[:, 0] < 0
        out, order = [rows[~chains]], [pos[~chains] * MAX_CHAIN]
        c, cpos = rows[chains], pos[chains]
        for m in np.unique(c[:, 0]):
            hit = c[:, 0] == m
            names, thread, keys = self._meta_keys[-1 - m]
            for i, name in enumerate(names):
                rec = np.empty((int(hit.sum()), _WIDTH), np.int64)
                rec[:, 0] = self._metas[(name,), thread, keys]
                rec[:, 1:3] = c[hit, _TIMES + i:_TIMES + i + 2]
                rec[:, 3] = _CHAIN_IDS + cpos[hit] * MAX_CHAIN + i
                rec[:, 4] = rec[:, 5] = c[hit, _ROOT]
                rec[:, _VALUE:] = c[hit, _VALUE:]
                out.append(rec)
                order.append(cpos[hit] * MAX_CHAIN + i)
        return np.concatenate(out)[np.argsort(np.concatenate(order),
                                              kind="stable")]

    def records(self, root: int | None = None) -> dict | None:
        """The retained records, oldest first, as columns: ``name`` and
        ``thread`` (str), ``start_ns``, ``end_ns``, ``span_id``,
        ``parent_id``, ``root_id`` and one per attribute key (-1 where a
        record lacks it).  With ``root``, only that root's records, or None
        if the ring dropped any of them."""
        with self._lock:
            self._flush()
            if root is not None and root in self._lost:
                return None
            rows, pos = self._rows()
            keys = list(self._meta_keys)
        if root is not None:
            hit = rows[:, _ROOT] == root
            rows, pos = rows[hit], pos[hit]
        rows = self._expand(rows, pos)
        meta = rows[:, 0]
        out = {"name": np.array([k[0][0] for k in keys] or [""],
                                object)[meta],
               "thread": np.array([k[1] for k in keys] or [""],
                                  object)[meta]}
        out.update({c: rows[:, _ROW.index(c)] for c in _COLUMNS})
        for attr in {a for k in keys for a in k[2]}:
            pos = np.array([k[2].index(attr) if attr in k[2] else -1
                            for k in keys])[meta]
            got = rows[np.arange(len(rows)), _VALUE + np.maximum(pos, 0)]
            out[attr] = np.where(pos >= 0, got, -1)
        return out


RING = Ring()
_IDS = itertools.count(1)
_NEXT_ID = _IDS.__next__
_local = threading.local()


def _stack() -> list:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        _local.thread = threading.current_thread().name
        return _local.stack


def context() -> Context | None:
    """The innermost open span of this thread (or attached context)."""
    stack = _stack()
    return Context(*stack[-1]) if stack else None


def new_root() -> Context:
    """A root that is no span: the owner of spans on several threads (a
    serve queue)."""
    i = _NEXT_ID()
    return Context(i, i)


class attach:
    """Hang this thread's spans under ``ctx`` (from ``context()`` or
    ``new_root()`` on another thread) until exit; None attaches nothing."""

    def __init__(self, ctx: Context | None):
        self.ctx = ctx

    def __enter__(self):
        if self.ctx is not None:
            _stack().append(tuple(self.ctx))
        return self

    def __exit__(self, *exc):
        if self.ctx is not None:
            _stack().pop()
        return False


class span:
    """Time the enclosed host work as one span (see the module docstring).

    ``set(**attrs)`` adds attributes known only inside the span;
    ``discard()`` keeps it out of the ring (the profiler's trace still
    shows it).
    """

    __slots__ = ("name", "attrs", "span_id", "parent_id", "root_id",
                 "start_ns", "end_ns", "_ta", "_keep")

    def __init__(self, name: str, **attrs):
        self.name, self.attrs = name, attrs
        self._keep = True

    def __enter__(self):
        try:
            stack = _local.stack
        except AttributeError:
            stack = _stack()
        sid = self.span_id = _NEXT_ID()
        if stack:
            self.parent_id, self.root_id = stack[-1]
        else:
            self.parent_id = 0
            self.root_id = sid
        stack.append((sid, self.root_id))
        # an annotation stamps its start when constructed, and records
        # nothing unless a trace was running then
        ta = self._ta = (TraceAnnotation(self.name, **self.attrs)
                         if _tracing() else None)
        self.start_ns = now_ns()
        if ta is not None:
            ta.__enter__()
        return self

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)
        if self._ta is not None:
            self._ta.set_metadata(**attrs)

    def discard(self) -> None:
        self._keep = False

    def __exit__(self, *exc):
        self.end_ns = now_ns()
        if self._ta is not None:
            self._ta.__exit__(*exc)
        _local.stack.pop()
        if self._keep:
            RING.append(self.name, self.start_ns, self.end_ns, self.span_id,
                        self.parent_id, self.root_id, _local.thread,
                        self.attrs)
        return False


def record(name: str, start_ns: int, end_ns: int, *, root: int,
           **attrs) -> None:
    """A span timed by its caller, hung directly under ``root``; memory
    only (it started on another thread, so no annotation can cover it)."""
    try:
        thread = _local.thread
    except AttributeError:
        _stack()
        thread = _local.thread
    RING.append_chain((name,), (start_ns, end_ns), root, thread, attrs)


class Chain:
    """Consecutive stages with names and attribute keys fixed up front, for
    a hot path: ``record(times_ns, root, *values)`` records ``names[i]``
    from ``times_ns[i]`` to ``times_ns[i + 1]`` under ``root`` at a
    fraction of a span's cost.  Pair each stage with ``annotate`` for the
    trace."""

    __slots__ = ("names", "keys", "_metas", "_pad_values", "_pad_times")

    def __init__(self, names: tuple, keys: tuple = ()):
        if len(keys) > MAX_ATTRS or len(names) > MAX_CHAIN:
            raise ValueError(f"chain {names}: more than {MAX_ATTRS} "
                             f"attributes or {MAX_CHAIN} stages")
        self.names, self.keys = tuple(names), tuple(keys)
        self._metas: dict[str, int] = {}    # thread -> interned chain
        self._pad_values = _NO_VALUES[len(keys):]
        self._pad_times = _NO_TIMES[len(names):]

    def record(self, times_ns: tuple, root: int, *values: int) -> None:
        try:
            thread = _local.thread
        except AttributeError:
            _stack()
            thread = _local.thread
        m = self._metas.get(thread)
        if m is None:
            m = self._metas[thread] = RING._meta(self.names, thread,
                                                 self.keys)
        RING.put((-1 - m,) + times_ns + self._pad_times + (root,) + values
                 + self._pad_values)


_NO_ANNOTATION = contextlib.nullcontext()


def annotate(name: str, **attrs):
    """A span's trace annotation alone, for a stage that a ``Chain``
    records: a ``TraceAnnotation`` while a trace runs, else nothing."""
    return TraceAnnotation(name, **attrs) if _tracing() else _NO_ANNOTATION
