#!/usr/bin/env python3
"""Where a kept trace's device time goes: by scope, through the transforms,
and by operation, with each operation's shape.

``bench.program_trace`` matches a scope only as a whole component of an
operation's scope path. A scope opened inside a function that JAX
transforms reaches the path wrapped by the transforms: the model's ``conv``
(``repro.models.cnn._conv``) reads ``.../vmap(jvp(jvp(conv)))/...`` in the
local step, so ``program_trace.scope_ms_per_round(pt, "conv", ...)`` finds
nothing. This reader unwraps each component before it matches, and lists
the operations with the most device time with the shape and category that
the profiler records for them.

    python3 bench/scope_report.py <trace.xplane.pb[.gz]> [--scope conv ...] \\
        [--top 10]

The trace is one that ``bench/program_trace.py --keep`` brought back (the
benchmark's traced window, with the program's ``repro.*`` spans). It prints
one JSON object: the rounds in the window (``repro.dispatch`` spans in it
times the ``rounds`` of ``repro.chunk``), the busy device time per round,
the device time per round under each scope, the operations with the most
time and the busy share of each operation category. Times are per round
and averaged over the devices; the operations' shapes are the first
device's.
"""
from __future__ import annotations

import gzip
import os
import re
import shutil
import sys
import tempfile
from typing import Dict, List, Optional, Tuple

if __package__ in (None, ""):
    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import program_trace as P  # noqa: E402
from bench import trace  # noqa: E402

_WRAPPER = re.compile(r"^(\w+\()+|\)+$")


def unwrap(component: str) -> str:
    """A scope path component without the transforms around it:
    ``vmap(jvp(transpose(jvp(conv))))`` -> ``conv``."""
    return _WRAPPER.sub("", component)


def in_scope(path: Optional[str], scope: str) -> bool:
    """Whether ``scope`` is one component of the path, once unwrapped."""
    return path is not None and any(unwrap(c) == scope
                                    for c in path.split("/"))


def rounds_in_window(pt: P.ProgramTrace, window: trace.Interval) -> int:
    """Rounds the window's chunks ran: its ``repro.dispatch`` spans times
    the rounds per chunk that ``repro.chunk`` records."""
    per_chunk = {int(s.args["rounds"]) for s in pt.program
                 if s.name == "repro.chunk" and "rounds" in s.args}
    if len(per_chunk) != 1:
        raise ValueError(f"rounds per chunk not one number: {per_chunk}")
    dispatch = [s for s in pt.program if s.name == "repro.dispatch"
                and window[0] <= s.start and s.end <= window[1]]
    return len(dispatch) * per_chunk.pop()


def scope_ms_per_round(pt: P.ProgramTrace, scope: str,
                       window: trace.Interval,
                       rounds: int) -> Optional[float]:
    """``program_trace.scope_ms_per_round`` with the scope matched through
    the transforms; None where no operation carries it."""
    per_dev = []
    for ops, scope_of in zip(pt.trace.devices, pt.scopes):
        hits = [o for o in ops if in_scope(scope_of.get(o.name), scope)]
        if hits:
            per_dev.append(trace.busy_ns(hits, window))
    if not per_dev or rounds <= 0:
        return None
    return sum(per_dev) / len(per_dev) / rounds / 1e6


def op_info(path: str, plane: str = "/device:TPU:0"
            ) -> Dict[str, Tuple[str, str]]:
    """Each operation's (shape, category) on one device plane, by the name
    ``bench.trace`` gives it: the ``shape_with_layout`` and
    ``hlo_category`` stats of its event metadata, the layouts left out."""
    space = P._xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = {}
    for pl in space.planes:
        if not pl.name.startswith(plane):
            continue
        names = {e.value.id: e.value.name for e in pl.stat_metadata}
        for entry in pl.event_metadata:
            st = {names.get(s.metadata_id):
                  s.str_value or names.get(s.ref_value, "")
                  for s in entry.value.stats}
            shape = re.sub(r"\{[^}]*\}", "", st.get("shape_with_layout", ""))
            out[trace.short_name(entry.value.name)] = (
                shape, st.get("hlo_category", ""))
    return out


def op_ns(ops: List[trace.Op], window: trace.Interval) -> Dict[str, int]:
    """Each operation name's time inside the window."""
    tot = {}
    for o in ops:
        s, e = max(o.start, window[0]), min(o.end, window[1])
        if e > s:
            tot[o.name] = tot.get(o.name, 0) + e - s
    return tot


def report(pt: P.ProgramTrace, info: Dict[str, Tuple[str, str]],
           scopes: List[str], top: int = 10) -> dict:
    window = pt.trace.window
    rounds = rounds_in_window(pt, window)
    n = max(len(pt.trace.devices), 1)
    busy = sum(trace.busy_ns(ops, window) for ops in pt.trace.devices) / n
    tot = op_ns(pt.trace.devices[0], window) if pt.trace.devices else {}
    scope_of = pt.scopes[0] if pt.scopes else {}
    by_cat = {}
    for name, ns in tot.items():
        cat = info.get(name, ("", ""))[1] or "unknown"
        by_cat[cat] = by_cat.get(cat, 0) + ns
    all_ns = sum(tot.values())
    return {
        "rounds": rounds,
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_ms_per_round": busy / rounds / 1e6 if rounds else None,
        "scope_ms_per_round": {
            s: scope_ms_per_round(pt, s, window, rounds) for s in scopes},
        "top_ops": [
            {"name": name, "ms_per_round": ns / rounds / 1e6 if rounds
             else None, "shape": info.get(name, ("", ""))[0],
             "category": info.get(name, ("", ""))[1],
             "scope": scope_of.get(name)}
            for name, ns in sorted(tot.items(), key=lambda kv: -kv[1])[:top]],
        "category_share_of_op_time": {
            c: ns / all_ns for c, ns in
            sorted(by_cat.items(), key=lambda kv: -kv[1])} if all_ns else {},
    }


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--scope", nargs="*",
                    default=["conv", "local_phase", "comm_phase"])
    ap.add_argument("--top", type=int, default=10)
    args = ap.parse_args(argv)
    with tempfile.TemporaryDirectory(prefix="scope_report_") as d:
        path = args.trace
        if path.endswith(".gz"):
            path = os.path.join(d, "trace.xplane.pb")
            with gzip.open(args.trace, "rb") as src, open(path, "wb") as out:
                shutil.copyfileobj(src, out)
        pt = P.load(path)
        info = op_info(path)
    print(json.dumps(report(pt, info, args.scope, args.top)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
