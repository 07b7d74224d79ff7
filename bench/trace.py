"""Reduction of a profiler trace to the benchmark's device and host numbers.

A :class:`Recorder` runs the profiler in memory over a bounded window;
its trace becomes a :class:`Trace`: per chip, the intervals of the
operations that ran on it; per host thread, the spans and Python frames
that were open. The functions below compute, over the window
``[lo, hi)``:

* ``busy_ns``: the union of a device's operation intervals;
* ``sum_by_name``: device time per operation name (top device ops, the
  chunk runner's executions, a kernel's executions);
* ``frames``: the outermost Python frames of a function (the profiler's
  Python tracer names a frame ``$file.py:line func``);
* ``idle_gaps``: the device's idle gaps, each named by the innermost
  host span open over most of it.

Everything is plain data after ``from_profile``, so ``bench/test_bench.py`` checks
the arithmetic on a trace written by hand.
"""

from __future__ import annotations

import dataclasses
from collections import defaultdict

# chip plane names start with this ("/device:TPU:0", ...); the host plane
# is "/host:CPU"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
# lines of a device plane that hold operation executions (TPU: "XLA Ops";
# module executions sit on "XLA Modules" and cover the same time)
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: int  # ns
    dur: int  # ns

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    # device plane name -> line name -> events
    devices: dict
    # host line (thread) name -> events
    host: dict
    lo: int
    hi: int

    def device_names(self) -> list[str]:
        """Chip planes in chip order."""
        return sorted(self.devices, key=lambda d: (len(d), d))

    def ops(self, dev: str) -> list[Event]:
        return [e for ln in OP_LINES for e in self.devices[dev].get(ln, ())]

    def modules(self, dev: str) -> list[Event]:
        return [e for ln in MODULE_LINES
                for e in self.devices[dev].get(ln, ())]


def from_profile(pd, lo: int = 0, hi: int = 0) -> Trace:
    """Plain data of a ``jax.profiler.ProfileData``; ``[lo, hi)`` is the
    window, in ns on the trace's clock."""
    devices: dict = {}
    host: dict = defaultdict(list)
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = defaultdict(list)
            for line in plane.lines:
                for e in line.events:
                    lines[line.name].append(
                        Event(e.name, int(e.start_ns), int(e.duration_ns)))
            devices[plane.name] = dict(lines)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    host[line.name].append(
                        Event(e.name, int(e.start_ns), int(e.duration_ns)))
    return Trace(devices=devices, host=dict(host), lo=lo, hi=hi)


class Recorder:
    """A profiler session kept in memory (nothing is written to disk).

    It starts at construction and stops at ``stop()`` or, at the latest,
    ``cap_s`` seconds later from a timer thread, so a trace never outgrows
    what one run can read back. The window is [the ``bench.mark`` span at
    the start, the moment the session stopped), on the trace's clock.
    """

    def __init__(self, cap_s: float):
        import threading
        import time

        import jax
        from jax._src.lib import _profiler  # jax 0.9.0: the session object

        self._time = time.perf_counter
        self._lock = threading.Lock()
        self._xspace = None
        self._session = _profiler.ProfilerSession(
            jax.profiler.ProfileOptions())
        self._t0 = self._time()
        with jax.profiler.TraceAnnotation("bench.mark"):
            pass
        self._t1 = None
        self._timer = threading.Timer(cap_s, self.stop)
        self._timer.daemon = True
        self._timer.start()

    @property
    def stopped(self) -> bool:
        return self._xspace is not None

    def stop(self) -> None:
        with self._lock:
            if self._xspace is None:
                self._t1 = self._time()
                self._xspace = self._session.stop()

    def finish(self) -> Trace:
        """Stop (if still running), join the timer, and reduce the trace."""
        from jax.profiler import ProfileData

        self._timer.cancel()
        self.stop()
        self._timer.join()
        tr = from_profile(ProfileData.from_serialized_xspace(self._xspace))
        self._xspace = b""
        mark = [e for evs in tr.host.values() for e in evs
                if e.name == "bench.mark"]
        if not mark:
            raise ValueError("no bench.mark span in the trace")
        tr.lo = mark[0].start
        tr.hi = tr.lo + int((self._t1 - self._t0) * 1e9)
        return tr


def union_ns(events, lo: int, hi: int) -> int:
    """Length of the union of the events' intervals clipped to [lo, hi)."""
    iv = sorted((max(e.start, lo), min(e.end, hi)) for e in events
                if e.end > lo and e.start < hi)
    total, cur_s, cur_e = 0, None, None
    for s, t in iv:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, t
        else:
            cur_e = max(cur_e, t)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_ns(tr: Trace, dev: str) -> int:
    return union_ns(tr.ops(dev), tr.lo, tr.hi)


def in_window(tr: Trace, events):
    return [e for e in events if e.start >= tr.lo and e.start < tr.hi]


def op_name(name: str) -> str:
    """Short name of a device op: the HLO instruction's name, without the
    instruction text the TPU trace gives (``%fusion.3 = ... fusion(...)``
    reads ``fusion.3``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def sum_by_name(events, short: bool = False) -> dict[str, int]:
    out: dict = defaultdict(int)
    for e in events:
        out[op_name(e.name) if short else e.name] += e.dur
    return dict(out)


def _outermost(events):
    """Events not nested in another event of the list (same thread)."""
    out, end = [], None
    for e in sorted(events, key=lambda e: (e.start, -e.dur)):
        if end is not None and e.start < end:
            continue
        out.append(e)
        end = e.end
    return out


def frames(tr: Trace, func: str, file: str | None = None) -> list[Event]:
    """The outermost Python-tracer frames of ``func`` (``$<file>:<line>
    <func>``) that lie wholly inside the window."""
    out = []
    for evs in tr.host.values():
        hits = []
        for e in evs:
            if not e.name.startswith("$") or " " not in e.name:
                continue
            where, name = e.name[1:].rsplit(" ", 1)
            if name == func and (file is None
                                 or where.split(":")[0] == file):
                hits.append(e)
        out += [e for e in _outermost(hits)
                if e.start >= tr.lo and e.end <= tr.hi]
    return out


def gaps(events, lo: int, hi: int, min_ns: int = 0):
    """Idle intervals of a device between lo and hi, longest first."""
    iv = sorted((e.start, e.end) for e in events if e.end > lo
                and e.start < hi)
    out, cur = [], lo
    for s, t in iv:
        if s > cur and s - cur >= min_ns:
            out.append((cur, min(s, hi)))
        cur = max(cur, t)
    if hi > cur and hi - cur >= min_ns:
        out.append((cur, hi))
    return sorted(out, key=lambda g: g[0] - g[1])


def host_activity(tr: Trace, s: int, t: int) -> str:
    """Name of the innermost host span or frame that covers most of
    [s, t), over all host threads; "host idle" when none does."""
    best, best_key = "host idle", None
    for evs in tr.host.values():
        for e in evs:
            cover = min(e.end, t) - max(e.start, s)
            if cover * 2 < (t - s):
                continue
            key = -e.dur  # the innermost of those covering most of it
            if best_key is None or key > best_key:
                best, best_key = e.name, key
    return best


def idle_gaps(tr: Trace, dev: str, top: int = 10):
    """The ``top`` longest idle gaps of ``dev``: [(host activity, s)]."""
    out = []
    for s, t in gaps(tr.ops(dev), tr.lo, tr.hi)[:top]:
        out.append([host_activity(tr, s, t), (t - s) / 1e9])
    return out
