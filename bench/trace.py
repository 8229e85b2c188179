"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps, per
device, the intervals in which an XLA operation ran (the "XLA Ops" line of
each ``/device:TPU:<n>`` plane), and the benchmark's own host spans
(``bench.*`` annotations). Where no device plane exists (the CPU backend
runs XLA's operations on host threads) the operations of every host thread
that carry an ``hlo_op`` stat form one device; tests use that.

Everything after ``load`` is interval arithmetic on plain tuples, so it is
tested on hand-made intervals as well as on a trace recorded on the CPU.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import warnings
from typing import Iterable, List, Sequence, Tuple

Interval = Tuple[int, int]  # [start_ns, end_ns)

SPAN_PREFIX = "bench."
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all", "allgather", "allreduce")
# (XLA's TPU traces name them with '-', the CPU backend's with '_')


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start: int
    end: int


@dataclasses.dataclass
class Trace:
    devices: List[List[Op]]  # one list per device, sorted by start
    spans: List[Op]          # the benchmark's host spans, sorted by start

    @property
    def window(self) -> Interval:
        """From the start of the first host span to the end of the last."""
        if not self.spans:
            raise ValueError("trace holds no bench.* host spans")
        return (min(s.start for s in self.spans),
                max(s.end for s in self.spans))


CONTAINERS = ("while", "conditional", "call")


def short_name(text: str) -> str:
    """An XLA operation's name from the event's text: TPU traces name an
    event by the whole HLO instruction ("%fusion.12 = f32[...] fusion(...)"),
    the CPU backend by the name alone."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def base_name(name: str) -> str:
    """'fusion.12' -> 'fusion'."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def is_container(name: str) -> bool:
    """Control flow whose interval covers the operations of its body."""
    return base_name(name) in CONTAINERS


def is_collective(name: str) -> bool:
    low = name.lower().replace("_", "-")
    return any(c in low for c in COLLECTIVES)


def _stats(ev) -> dict:
    out = {}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        items = list(ev.stats)
    for item in items:
        try:
            k, v = item
        except (TypeError, ValueError):
            continue
        out[k] = v
    return out


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    devices, spans, host_ops = [], [], []
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/device:") and ":" in name[len("/device:"):]:
            kind, _, idx = name[len("/device:"):].partition(":")
            if kind == "CPU" or not idx.isdigit():
                continue
            ops = []
            for line in plane.lines:
                # "XLA Ops": every operation as it ran; "Async XLA Ops":
                # the in-flight span of asynchronous ones, kept only for
                # collectives (a transfer in flight is not compute)
                if line.name not in ("XLA Ops", "Async XLA Ops"):
                    continue
                for ev in line.events:
                    op = short_name(ev.name)
                    if is_container(op) or (line.name != "XLA Ops"
                                            and not is_collective(op)):
                        continue
                    ops.append(Op(op, int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns)))
            devices.append((int(idx), sorted(ops, key=lambda o: o.start)))
            continue
        if not name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                start = int(ev.start_ns)
                end = int(ev.start_ns + ev.duration_ns)
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append(Op(ev.name, start, end))
                elif ev.duration_ns > 0 and "hlo_op" in _stats(ev):
                    op = short_name(ev.name)
                    if not is_container(op):
                        host_ops.append(Op(op, start, end))
    if devices:
        per_dev = [ops for _, ops in sorted(devices)]
    else:
        per_dev = [sorted(host_ops, key=lambda o: o.start)]
    return Trace(devices=per_dev, spans=sorted(spans, key=lambda o: o.start))


# -- interval arithmetic ------------------------------------------------------
def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def measure(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in union(intervals))


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of union(a) not covered by union(b)."""
    out, b = [], union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur:
                continue
            if bs >= e:
                break
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def busy_ns(ops: Sequence[Op], window: Interval) -> int:
    """Length of the union of the operations' intervals inside the window."""
    return measure(clip(((o.start, o.end) for o in ops), *window))


def gaps(ops: Sequence[Op], window: Interval) -> List[Interval]:
    """The idle intervals of one device inside the window."""
    lo, hi = window
    busy = union(clip(((o.start, o.end) for o in ops), lo, hi))
    return subtract([(lo, hi)], busy)


def kernel_time(ops: Sequence[Op], prefix: str,
                window: Interval) -> Tuple[int, int]:
    """(summed duration in ns, number of events) of the operations whose
    name starts with ``prefix`` and that start inside the window."""
    lo, hi = window
    hits = [o for o in ops if o.name.startswith(prefix) and lo <= o.start < hi]
    return sum(o.end - o.start for o in hits), len(hits)


def kernel_ms_per_round(devices: Sequence[Sequence[Op]], prefix: str,
                        window: Interval, rounds: int):
    """Device time in ms per round of the operations named ``prefix``,
    averaged over the devices that ran them; None where none did."""
    per_dev = []
    for ops in devices:
        ns, calls = kernel_time(ops, prefix, window)
        if calls:
            per_dev.append(ns)
    if not per_dev or rounds == 0:
        return None
    return sum(per_dev) / len(per_dev) / rounds / 1e6


def exposed_collective_ns(ops: Sequence[Op], window: Interval) -> int:
    """Time inside the window in which a collective runs on the device and
    no other operation does."""
    coll = clip(((o.start, o.end) for o in ops if is_collective(o.name)),
                *window)
    comp = clip(((o.start, o.end) for o in ops
                 if not is_collective(o.name)), *window)
    return measure(subtract(coll, comp))


def top_ops(devices: Sequence[Sequence[Op]], window: Interval,
            n: int = 10) -> List[list]:
    """The n operation names with the most device time in the window,
    averaged over devices: [[name, seconds], ...]."""
    tot = {}
    for ops in devices:
        for o in ops:
            s, e = max(o.start, window[0]), min(o.end, window[1])
            if e > s:
                tot[o.name] = tot.get(o.name, 0) + (e - s)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9 / len(devices)] for name, ns in best]


def labelled_gaps(devices: Sequence[Sequence[Op]], spans: Sequence[Op],
                  window: Interval, n: int = 10) -> List[list]:
    """The n longest idle gaps over all devices, each named by the host span
    that covers most of it: [["<span>@dev<i>", seconds], ...]."""
    out = []
    for i, ops in enumerate(devices):
        for s, e in gaps(ops, window):
            best, cover = "host:other", 0
            for sp in spans:
                c = min(e, sp.end) - max(s, sp.start)
                if c > cover:
                    best, cover = sp.name, c
            out.append((e - s, f"{best}@dev{i}"))
    out.sort(key=lambda t: -t[0])
    return [[label, ns / 1e9] for ns, label in out[:n]]
