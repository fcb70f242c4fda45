"""In-memory spans at the program's own boundaries.

    with span("repro.stream.pack", nbytes=n) as s:
        ...
    s.ns  # the span's own duration, read by the caller

A span stamps ``time.perf_counter_ns()`` on entry and exit, opens a
``jax.profiler.TraceAnnotation`` of its name while the profiler records
(so that a profiled run shows it beside the device's ops, on the
profiler's clock), and on exit appends a :class:`Record` to one bounded
ring for the process. Each record names the
span enclosing it on its thread (``parent``) and the frame it belongs to:
a span opened with ``new_frame=True`` starts a frame whose id is its own
sequence number, and every span nested in it inherits that id.

Work handed to another thread carries its submitter's :func:`current`
along, and the worker files what it timed with :func:`record`, so the
worker's record names the span that caused it and shares its frame.

Counters: :func:`count` files one reading of a named counter (a number
of things, not an interval) under the frame open on the calling thread;
:func:`counts` returns them, kept in a ring of their own.

Every record is on the ``perf_counter_ns`` clock, and every name starts
with ``repro.``. :func:`set_enabled` (``False``) stops all recording; a
disabled span still stamps its own start and end, which callers read."""

from __future__ import annotations

import collections
import itertools
import struct
import sys
import threading
from time import perf_counter_ns
from typing import NamedTuple

CAPACITY = 65536  # records kept; the oldest drop first
THREAD_NAMES = 1024  # thread names kept before those of ended threads drop
_Annotation = None  # jax.profiler.TraceAnnotation, once jax is imported


def _profiling() -> bool:
    """Whether the profiler is recording now; an annotation made while it
    is not records nothing, so none is made. Before jax is imported nothing
    can be profiling; after, this name is bound to jax's own check."""
    global _profiling, _Annotation
    if "jax.profiler" not in sys.modules:
        return False
    from jax.profiler import TraceAnnotation
    _Annotation = TraceAnnotation
    _profiling = TraceAnnotation.is_enabled
    return _profiling()


class Record(NamedTuple):
    name: str
    t0: int            # perf_counter_ns at the start
    t1: int            # and at the end
    thread: str        # name of the thread the interval was timed on
                       # (``thread <ident>`` once its name was dropped)
    seq: int           # this record's sequence number
    parent: int | None  # seq of the span enclosing it (None at the root)
    frame: int | None  # seq of the frame's root span
    nbytes: int        # bytes the interval moved or packed (0 if none)


class Count(NamedTuple):
    name: str
    value: int
    t: int             # perf_counter_ns when it was counted
    frame: int | None  # seq of the frame open on the counting thread


# A kept record is packed into bytes, which the garbage collector does not
# track: kept as tuples, every record would count towards a collection
# until the ring fills, and the extra collections (full ones among them)
# would cost the program more than the recording does. Fields: t0, t1, seq,
# parent, frame (0 for none), nbytes, name id, thread ident.
_ROW = struct.Struct("<qqqqqqiQ")
_pack = _ROW.pack
_names: list[str] = []  # id -> span name; span names are a fixed few
_ids: dict[str, int] = {}
_ids_lock = threading.Lock()
# thread ident -> name, filled as each thread records its first interval.
# Threads come and go (the runtime's workers idle out and new ones start),
# so the table keeps only live threads' names once it grows past
# THREAD_NAMES; a record of an ended thread whose name was dropped, or
# whose ident a later thread took over, reads as that ident.
_threads: dict[int, str] = {}


def _id(name: str) -> int:
    i = _ids.get(name)
    if i is None:
        with _ids_lock:
            i = _ids.get(name)
            if i is None:
                _names.append(name)
                i = _ids[name] = len(_names) - 1
    return i


class _Local(threading.local):
    def __init__(self):
        self.stack: list[tuple[int, int | None]] = []  # (seq, frame), open
        t = threading.current_thread()
        self.thread = t.ident
        if len(_threads) >= THREAD_NAMES:
            live = {th.ident for th in threading.enumerate()}
            for ident in [i for i in list(_threads) if i not in live]:
                _threads.pop(ident, None)
        _threads[t.ident] = t.name


_records: collections.deque[bytes] = collections.deque(maxlen=CAPACITY)
_counts: collections.deque[tuple] = collections.deque(maxlen=CAPACITY)
_seq = itertools.count(1)
_local = _Local()
_enabled = True


class span:
    """Context manager timing one interval; see the module docstring."""

    __slots__ = ("name", "nbytes", "new_frame", "t0", "t1", "_seq",
                 "_parent", "_frame", "_ann", "_loc")

    def __init__(self, name: str, nbytes: int = 0, *,
                 new_frame: bool = False):
        self.name = name
        self.nbytes = nbytes
        self.new_frame = new_frame

    def __enter__(self) -> "span":
        if _enabled:
            self._loc = loc = _local
            stack = loc.stack
            self._seq = seq = next(_seq)
            self._parent, frame = stack[-1] if stack else (None, None)
            self._frame = frame = seq if self.new_frame else frame
            stack.append((seq, frame))
            if _profiling():
                self._ann = ann = _Annotation(self.name)
                ann.__enter__()
            else:
                self._ann = None
        else:
            self._loc = None
        self.t0 = perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = t1 = perf_counter_ns()
        loc = self._loc
        if loc is not None:
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
            loc.stack.pop()
            _records.append(_pack(self.t0, t1, self._seq, self._parent or 0,
                                  self._frame or 0, self.nbytes,
                                  _id(self.name), loc.thread))

    @property
    def ns(self) -> int:
        return self.t1 - self.t0


def current() -> tuple[int | None, int | None]:
    """``(frame, parent)`` for work submitted from the calling thread: its
    innermost open span's frame and sequence number."""
    stack = _local.stack
    if not stack:
        return None, None
    seq, frame = stack[-1]
    return frame, seq


def record(name: str, t0_ns: int, t1_ns: int, *, parent: int | None,
           frame: int | None, nbytes: int = 0) -> None:
    """File an interval timed elsewhere (``perf_counter_ns`` stamps), on
    behalf of the span ``parent`` of frame ``frame``."""
    if _enabled:
        _records.append(_pack(t0_ns, t1_ns, next(_seq), parent or 0,
                              frame or 0, nbytes, _id(name), _local.thread))


def count(name: str, value: int) -> None:
    """File one reading of counter ``name`` under the calling thread's
    open frame."""
    if _enabled:
        stack = _local.stack
        frame = stack[-1][1] if stack else None
        _counts.append((_id(name), int(value), perf_counter_ns(), frame))


def counts() -> list[Count]:
    """The counter readings held, oldest first."""
    names = _names
    return [Count(names[n], v, t, f) for n, v, t, f in list(_counts)]


def snapshot() -> list[Record]:
    """The records held, oldest first."""
    names, threads = _names, dict(_threads)
    return [Record(names[n], t0, t1, threads.get(th) or f"thread {th}", seq,
                   parent or None, frame or None, nbytes)
            for t0, t1, seq, parent, frame, nbytes, n, th
            in _ROW.iter_unpack(b"".join(list(_records)))]


def clear() -> None:
    _records.clear()
    _counts.clear()


def set_enabled(on: bool) -> None:
    global _enabled
    _enabled = bool(on)
