#!/usr/bin/env python3
"""The program's own spans and scopes in a profiler trace, and what they say.

The program annotates each chunk's host work with ``repro.*`` spans
(``repro.api.session``: ``repro.chunk`` and its children, each with the
chunk's first round as ``chunk`` and counters as args) and names the round's
phases with ``jax.named_scope`` (``repro.core.coordinator._round``:
``local_phase``, ``comm_phase``, ``global_sync``, ``reseat``). Both land in
the same ``.xplane.pb`` as the device planes, on one clock.

``load`` adds to the benchmark's reduction (``bench.trace.load``: the
device operations, the ``bench.*`` spans and the window) what it lacks: the
``repro.*`` host spans with their args, and each device operation's scope
path (the ``tf_op`` stat of the operation's event metadata on the TPU; the
CPU backend records none). The functions after it read the quantities a
per-layer metric would report: device time per round under a scope, the
host time per chunk of a span, the device-idle part of ``repro.fetch`` per
chunk, the transfers per chunk, and the idle time of the window attributed
to the innermost program span over it.

Run as a script it measures one cell on the chip and prints one JSON object:

    python3 bench/program_trace.py --workload <cell> --seed <n> \\
        [--untraced-seconds 10] [--keep <dir>]

Set-up is the benchmark's (``bench/harness.py``); then an untraced window
and the benchmark's traced window (its ``bench.*`` spans included), each
with its median chunk period, and the reduction of the traced one. With
``--keep`` the trace's ``.xplane.pb`` is copied, gzipped, into that
directory.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import heapq
import os
import statistics
import sys
import warnings
from typing import Dict, List, Optional, Tuple

if __package__ in (None, ""):
    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import trace  # noqa: E402

PREFIX = "repro."
SCOPES = ("local_phase", "comm_phase", "global_sync", "reseat")
Interval = Tuple[int, int]


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int
    end: int
    args: Dict[str, object]

    @property
    def chunk(self):
        """The first round of the span's chunk."""
        return self.args.get("chunk", self.args.get("step_num"))


@dataclasses.dataclass
class ProgramTrace:
    trace: trace.Trace     # the benchmark's reduction of the same file
    program: List[Span]    # repro.* host spans, sorted by start
    # per device of trace.devices: operation name -> scope path, None where
    # operations of that name carry different scopes (two programs' ops)
    scopes: List[Dict[str, Optional[str]]]


@functools.lru_cache(maxsize=1)
def _xspace_class():
    """The few fields of the profiler's ``XSpace`` protobuf that hold the
    operations' scopes: each plane's event metadata (an operation's name
    and its stats, ``tf_op`` among them) and stat metadata (the stats'
    names, and strings that stats refer to). ``ProfileData`` gives an
    event's own stats but not its metadata's."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane_scopes.proto", package="bench_xplane_scopes",
        syntax="proto3")
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    i64, u64, text, sub = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING, \
        F.TYPE_MESSAGE
    # field numbers as in tsl/profiler/protobuf/xplane.proto; a map field is
    # a repeated (key = 1, value = 2) entry on the wire
    for msg, fields in (
            ("XStat", [("metadata_id", 1, i64, one, ""),
                       ("str_value", 5, text, one, ""),
                       ("ref_value", 7, u64, one, "")]),
            ("XEventMetadata", [("name", 2, text, one, ""),
                                ("stats", 5, sub, many, "XStat")]),
            ("XStatMetadata", [("id", 1, i64, one, ""),
                               ("name", 2, text, one, "")]),
            ("EventEntry", [("key", 1, i64, one, ""),
                            ("value", 2, sub, one, "XEventMetadata")]),
            ("StatEntry", [("key", 1, i64, one, ""),
                           ("value", 2, sub, one, "XStatMetadata")]),
            ("XPlane", [("name", 2, text, one, ""),
                        ("event_metadata", 4, sub, many, "EventEntry"),
                        ("stat_metadata", 5, sub, many, "StatEntry")]),
            ("XSpace", [("planes", 1, sub, many, "XPlane")])):
        m = fd.message_type.add(name=msg)
        for name, number, kind, label, type_name in fields:
            f = m.field.add(name=name, number=number, type=kind, label=label)
            if type_name:
                f.type_name = f".bench_xplane_scopes.{type_name}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane_scopes.XSpace"))


def scopes(path: str) -> Dict[str, Dict[str, Optional[str]]]:
    """Per plane, each operation's scope path by its name as
    ``bench.trace`` names it: the ``tf_op`` stat of the event's metadata,
    without the ``:<type>`` suffix
    ("jit(round_chunk)/while/body/closed_call/local_phase/..."). A name
    whose operations carry different scopes (two programs each with a
    ``fusion.3``) maps to None; a plane without the stat maps to {}."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = {}
    for plane in space.planes:
        names = {e.value.id: e.value.name for e in plane.stat_metadata}
        tf_op = next((k for k, v in names.items() if v == "tf_op"), None)
        got = {}
        for entry in plane.event_metadata:
            for st in entry.value.stats:
                if tf_op is not None and st.metadata_id == tf_op:
                    path_ = st.str_value or names.get(st.ref_value, "")
                    op = trace.short_name(entry.value.name)
                    path_ = path_.rsplit(":", 1)[0]
                    got[op] = path_ if got.get(op, path_) == path_ else None
        out[plane.name] = got
    return out


def _device_index(plane: str) -> Optional[int]:
    """The index of a device plane that ``bench.trace.load`` reads."""
    kind, _, idx = plane[len("/device:"):].partition(":")
    if not plane.startswith("/device:") or kind == "CPU" \
            or not idx.isdigit():
        return None
    return int(idx)


def program_spans(path: str) -> List[Span]:
    """The ``repro.*`` host events of the trace, with their args."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(PREFIX):
                    continue
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore", DeprecationWarning)
                    args = {k: v for k, v in ev.stats
                            if not k.startswith("_")}
                out.append(Span(ev.name, int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns), args))
    return sorted(out, key=lambda s: s.start)


def load(path: str) -> ProgramTrace:
    """The benchmark's reduction of the trace at ``path`` (a ``.xplane.pb``
    or the directory ``jax.profiler`` wrote), the program's spans and the
    device operations' scopes."""
    if os.path.isdir(path):
        path = trace.find_xplane(path)
    tr = trace.load(path)
    by_plane = scopes(path)
    planes = sorted((i, name) for name in by_plane
                    if (i := _device_index(name)) is not None)
    maps = [by_plane[name] for _, name in planes]
    if len(maps) != len(tr.devices):  # the CPU: no device plane, no scope
        maps = [{} for _ in tr.devices]
    return ProgramTrace(trace=tr, program=program_spans(path), scopes=maps)


# -- readings -----------------------------------------------------------------
def in_scope(path: Optional[str], scope: str) -> bool:
    """Whether ``scope`` is one component of the scope path."""
    return path is not None and f"/{scope}/" in f"/{path}/"


def scope_ms_per_round(pt: ProgramTrace, scope: str, window: Interval,
                       rounds: int) -> Optional[float]:
    """Device time in ms per round of the operations under ``scope`` inside
    the window, averaged over the devices; None where no operation carries
    the scope (a program without it)."""
    per_dev = []
    for ops, scope_of in zip(pt.trace.devices, pt.scopes):
        hits = [o for o in ops if in_scope(scope_of.get(o.name), scope)]
        if hits:
            per_dev.append(trace.busy_ns(hits, window))
    if not per_dev or rounds <= 0:
        return None
    return sum(per_dev) / len(per_dev) / rounds / 1e6


def innermost(program: List[Span]) -> List[Span]:
    """The program spans less the spans nested in them: each instant under
    a program span once, named (with the args) of the innermost span over
    it. One host thread's spans nest or follow each other."""
    spans = sorted(program, key=lambda s: (s.start, -s.end))
    out = []
    for i, s in enumerate(spans):
        inner = []
        for t in spans[i + 1:]:
            if t.start >= s.end:
                break
            if t.end <= s.end:
                inner.append((t.start, t.end))
        out += [Span(s.name, a, b, s.args)
                for a, b in trace.subtract([(s.start, s.end)], inner)]
    return sorted(out, key=lambda p: p.start)


def _inside(spans: List[Span], window: Interval) -> List[Span]:
    return [s for s in spans if window[0] <= s.start and s.end <= window[1]]


def _per_chunk(pieces: List[Span], name: str, value) -> List[float]:
    """``value`` of the pieces named ``name``, summed per chunk."""
    tot = {}
    for p in pieces:
        if p.name == name:
            tot[p.chunk] = tot.get(p.chunk, 0) + value(p)
    return list(tot.values())


def span_ms_per_chunk(program: List[Span], name: str,
                      window: Interval) -> Optional[float]:
    """Median over the window's chunks of the host time in ms in which
    ``name`` is the innermost program span (its spans nested in it left
    out); None where the program has no such span."""
    got = _per_chunk(innermost(_inside(program, window)), name,
                     lambda p: p.end - p.start)
    return statistics.median(got) / 1e6 if got else None


def _idle_ns(ops: List[trace.Op], window: Interval):
    """For an interval, the time in it and in the window in which the
    device runs none of ``ops``."""
    busy = trace.union(trace.clip(((o.start, o.end) for o in ops), *window))
    starts, ends = [s for s, _ in busy], [e for _, e in busy]

    def idle(s: int, e: int) -> int:
        s, e = max(s, window[0]), min(e, window[1])
        if e <= s:
            return 0
        lo, hi = bisect.bisect_right(ends, s), bisect.bisect_left(starts, e)
        return (e - s) - trace.measure(trace.clip(busy[lo:hi], s, e))
    return idle


def idle_ms_in_span(pt: ProgramTrace, name: str,
                    window: Interval) -> Optional[float]:
    """Median over the window's chunks of the device-idle time, in ms, in
    which ``name`` is the innermost program span, averaged over the
    devices."""
    pieces = innermost(_inside(pt.program, window))
    if not any(p.name == name for p in pieces) or not pt.trace.devices:
        return None
    idle = [_idle_ns(ops, window) for ops in pt.trace.devices]
    got = _per_chunk(pieces, name, lambda p: sum(
        f(p.start, p.end) for f in idle) / len(idle))
    return statistics.median(got) / 1e6


def idle_by_span(pt: ProgramTrace, window: Interval) -> Dict[str, int]:
    """Device-idle ns of the window, summed over devices and split by the
    innermost program span over each idle instant ("repro.chunk": the
    chunk's host work between its named children); "outside" where no
    program span is."""
    pieces = innermost(pt.program)
    out = dict.fromkeys(sorted({p.name for p in pieces}) + ["outside"], 0)
    for ops in pt.trace.devices:
        idle = _idle_ns(ops, window)
        total = idle(*window)
        for p in pieces:
            ns = idle(p.start, p.end)
            out[p.name] += ns
            total -= ns
        out["outside"] += total
    return out


def labelled_gaps(pt: ProgramTrace, window: Interval,
                  n: int = 10) -> List[list]:
    """``bench.trace.labelled_gaps`` over the program's spans: the n longest
    idle gaps, each named by the span that covers most of it, counting
    each instant under the innermost program span over it, or else under
    the ``bench.*`` span over it."""
    pieces = innermost(pt.program)
    program = trace.union((p.start, p.end) for p in pieces)
    named = [trace.Op(p.name, p.start, p.end) for p in pieces] + [
        trace.Op(b.name, s, e) for b in pt.trace.spans
        for s, e in trace.subtract([(b.start, b.end)], program)]
    # only the spans over the n longest gaps can name one of them
    longest = heapq.nlargest(n, (g for ops in pt.trace.devices
                                 for g in trace.gaps(ops, window)),
                             key=lambda g: g[1] - g[0])
    near = [sp for sp in named
            if any(sp.start < e and s < sp.end for s, e in longest)]
    return trace.labelled_gaps(pt.trace.devices, near, window, n)


def transfers_per_chunk(program: List[Span], window: Interval) -> dict:
    """Median per chunk of the host-to-device copies (``repro.to_device``)
    and device-to-host pulls (``repro.fetch``): transfers and MB."""
    out = {}
    for name, kind in (("repro.to_device", "h2d"), ("repro.fetch", "d2h")):
        got = [s.args for s in _inside(program, window)
               if s.name == name and f"{kind}_transfers" in s.args]
        out[f"{kind}_transfers"] = (statistics.median(
            a[f"{kind}_transfers"] for a in got) if got else None)
        out[f"{kind}_mb"] = (statistics.median(
            a[f"{kind}_bytes"] for a in got) / 1e6 if got else None)
    return out


def scope_shares(pt: ProgramTrace, window: Interval, n: int = 8) -> dict:
    """Shares of busy device time under each phase scope and under none,
    and the n unscoped operations with the most time (seconds, averaged
    over devices); names whose scope is ambiguous count as unscoped."""
    devices = pt.trace.devices
    busy = sum(trace.busy_ns(ops, window) for ops in devices)
    under = dict.fromkeys(SCOPES, 0)
    none, unscoped, ambiguous = 0, {}, set()
    for ops, scope_of in zip(devices, pt.scopes):
        for scope in SCOPES:
            under[scope] += trace.busy_ns(
                [o for o in ops if in_scope(scope_of.get(o.name), scope)],
                window)
        bare = [o for o in ops
                if not any(in_scope(scope_of.get(o.name), sc)
                           for sc in SCOPES)]
        none += trace.busy_ns(bare, window)
        for o in bare:
            s, e = max(o.start, window[0]), min(o.end, window[1])
            if e > s:
                unscoped[o.name] = unscoped.get(o.name, 0) + e - s
                if o.name in scope_of and scope_of[o.name] is None:
                    ambiguous.add(o.name)
    top = sorted(unscoped.items(), key=lambda kv: -kv[1])[:n]
    return {"busy_s": busy / 1e9 / max(len(devices), 1),
            "share": {k: v / busy if busy else None
                      for k, v in under.items()},
            "unscoped_share": none / busy if busy else None,
            "unscoped_top": [[k, v / 1e9 / len(devices)] for k, v in top],
            "ambiguous": sorted(ambiguous)}


def report(pt: ProgramTrace, window: Interval, rounds: int) -> dict:
    """Every reading of one traced window."""
    # a chunk per dispatch: the window opens inside the first repro.chunk
    dispatch = [s for s in _inside(pt.program, window)
                if s.name == "repro.dispatch"]
    idle = idle_by_span(pt, window)
    total_idle = sum(idle.values())
    n, chips = max(len(dispatch), 1), max(len(pt.trace.devices), 1)
    return {
        "local_phase_ms": scope_ms_per_round(pt, "local_phase", window,
                                             rounds),
        "comm_phase_ms": scope_ms_per_round(pt, "comm_phase", window,
                                            rounds),
        "to_device_ms": span_ms_per_chunk(pt.program, "repro.to_device",
                                          window),
        "fetch_idle_ms": idle_ms_in_span(pt, "repro.fetch", window),
        "span_ms_per_chunk": {
            name: span_ms_per_chunk(pt.program, name, window)
            for name in sorted({s.name for s in pt.program})},
        "transfers_per_chunk": transfers_per_chunk(pt.program, window),
        "chunks": len(dispatch),
        "traced_in_window": sum(int(s.args.get("traced", 0))
                                for s in dispatch),
        "idle_ms_per_chunk": {k: v / 1e6 / n / chips
                              for k, v in sorted(idle.items())},
        "idle_in_program_share": (1 - idle["outside"] / total_idle
                                  if total_idle else None),
        "scopes": scope_shares(pt, window),
        "idle_gaps": labelled_gaps(pt, window),
    }


# -- the chip run -------------------------------------------------------------
def span_cost_us(n: int = 200_000) -> dict:
    """Host cost of one annotation with no profiler session active."""
    import time

    import jax

    out = {}
    for label, kw in (("no_args", {}), ("two_args", {"chunk": 1,
                                                     "h2d_bytes": 2})):
        t0 = time.perf_counter()
        for _ in range(n):
            with jax.profiler.TraceAnnotation("repro.cost", **kw):
                pass
        out[label] = (time.perf_counter() - t0) / n * 1e6
    return out


def main(argv=None) -> int:
    import argparse
    import gzip
    import json
    import shutil
    import tempfile

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--untraced-seconds", type=float, default=10.0)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)

    import jax

    from bench import harness
    from bench.spec import resolve
    from repro.launch.compile_cache import enable_compile_cache

    cell = resolve(args.workload)
    try:
        devices = harness.chips_for(cell)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = harness._CompileCounter.get()
    session, _ = harness.build_session(cell, args.seed, devices)
    harness.check_chunks(session, cell, args.seed)
    plain = harness.run_window(session, cell, args.untraced_seconds, 1,
                               counter)
    with tempfile.TemporaryDirectory(prefix="program_trace_") as d:
        with harness.host_spans(session) as end_wait:
            jax.profiler.start_trace(d)
            try:
                w = harness.run_window(session, cell, harness.TRACE_SECONDS,
                                       harness.TRACE_CHUNKS, counter,
                                       after_chunk=end_wait)
            finally:
                end_wait()
                jax.profiler.stop_trace()
        pt = load(d)
        if args.keep:
            os.makedirs(args.keep, exist_ok=True)
            dst = os.path.join(args.keep, f"{cell.name}.xplane.pb.gz")
            with open(trace.find_xplane(d), "rb") as src, \
                    gzip.open(dst, "wb") as out:
                shutil.copyfileobj(src, out)
    window = pt.trace.window
    busy = [trace.busy_ns(ops, window) for ops in pt.trace.devices]
    R = int(cell.traffic["rounds_per_call"])
    out = report(pt, window, w.rounds)
    out.update({
        "workload": cell.name, "seed": args.seed,
        "device": devices[0].device_kind, "rounds": w.rounds,
        "window_s": (window[1] - window[0]) / 1e9,
        "device_idle_share": 100.0 * (1 - sum(busy) / len(busy)
                                      / (window[1] - window[0])),
        "dispatch_ms": {"untraced": statistics.median(plain.dispatch_ms),
                        "traced": statistics.median(w.dispatch_ms)},
        "period_ms": {"untraced": statistics.median(plain.periods) * 1e3,
                      "traced": statistics.median(w.periods) * 1e3,
                      "untraced_chunks": plain.chunks,
                      "rounds_per_chunk": R},
        "compiles_in_windows": plain.compiles + w.compiles,
        "span_cost_us": span_cost_us()})
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
