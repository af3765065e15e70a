"""What the program writes into the profiler trace about itself.

``bench/core/trace.py`` reads the benchmark's own host spans.  This
module reads, from the same ``.xplane.pb``, the spans the program opens
itself (``engine.round`` and its phases ``engine.admit``,
``engine.plan``, ``engine.flush``, ``engine.dispatch.<kind>``,
``engine.sync``, ``engine.commit``; ``server.admit``,
``server.publish``), and each device op's ``jax.named_scope`` path
(``attention``, ``mlp``, ``logits``, ``kv_scatter``) inside the jitted
step it ran in (``jit_decode_step``, ``jit_chunk_step``, ...).

``load`` turns the trace into plain tuples and ``summarise`` does the
arithmetic on those alone, so a hand-made trace checks it on the CPU.
The metric readers call ``of(ctx)``; on a program that writes none of
these spans or scopes the numbers that need them read None.

  python -m bench.core.spans bench_out/trace          # summary as JSON
  python -m bench.core.spans bench_out/trace --ops 5   # scope lookup, by hand
"""

from __future__ import annotations

import bisect
import glob
import json
import re
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from bench.core.trace import _module_name, clip, intersect, length, union

PROGRAM = ("engine.", "server.")
ROUND = "engine.round"
# ops whose interval holds other ops' (a loop, a call): not leaves
ENCLOSING = re.compile(r"^%?(while|call|conditional)\b")
# an op's trace name starts with its HLO instruction's name
INSTRUCTION = re.compile(r"%?([\w.\-]+)")
Op = Tuple[str, float, float, str]       # (name, start_ns, end_ns, scope)


def load(log_dir: str) -> Dict:
    """{"devices": {plane: {"ops": [Op], "modules": [...]}}, "host":
    [...]} from the newest trace under ``log_dir``: the program's host
    spans and the window marks, every device op with its scope path
    (the ``op_name`` its program's HLO gives the op's instruction)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no trace under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    names = op_names(paths[-1])
    devices: Dict[str, Dict[str, list]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            ops, mods = lines.get("XLA Ops"), lines.get("XLA Modules")
            mods = [(e.name, e.start_ns, e.end_ns)
                    for e in (mods.events if mods else [])]
            ops = [(e.name, e.start_ns, e.end_ns)
                   for e in (ops.events if ops else [])]
            devices[plane.name] = {"ops": with_scopes(ops, mods, names),
                                   "modules": mods}
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                host += [(e.name, e.start_ns, e.end_ns) for e in ln.events
                         if e.name == "bench.mark"
                         or e.name.startswith(PROGRAM)]
    return {"devices": devices, "host": host}


def with_scopes(ops, mods, names: Dict[str, Dict[str, str]]) -> List[Op]:
    """Each op with the ``op_name`` of its instruction in the program
    whose launch it ran in ('' where the trace holds no such program or
    instruction); on a TPU an op's trace name is its HLO line,
    ``%fusion.116 = ...``."""
    mods = sorted(mods, key=lambda m: m[1])
    starts = [s for _, s, _ in mods]
    out = []
    for n, s, e in ops:
        k = bisect.bisect_right(starts, s) - 1
        table = names.get(mods[k][0], {}) if k >= 0 and s < mods[k][2] \
            else {}
        m = INSTRUCTION.match(n)
        out.append((n, s, e, (table.get(m.group(1)) or "") if m else ""))
    return out


def op_names(path: str) -> Dict[str, Dict[str, str]]:
    """{program: {instruction: op_name}} from the HLO the profiler keeps
    for each program it saw run (the ``Hlo Proto`` stats of the
    ``/host:metadata`` plane), read straight from the protobuf wire
    format: field numbers of XSpace/XPlane (tsl xplane.proto) and of
    HloProto/HloModuleProto/HloComputationProto/HloInstructionProto/
    OpMetadata (xla hlo.proto)."""
    data = memoryview(Path(path).read_bytes())
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(data):
        if f != 1:                                   # XSpace.planes
            continue
        top = list(_fields(plane))
        if next((bytes(v) for g, v in top if g == 2), b"") \
                != b"/host:metadata":                  # XPlane.name
            continue
        stat_names = {}
        for g, v in top:
            if g == 5:                               # XPlane.stat_metadata
                entry = dict(_fields(v))
                stat_names[entry.get(1)] = bytes(
                    dict(_fields(entry[2])).get(2, b""))
        for g, v in top:
            if g != 4:                               # XPlane.event_metadata
                continue
            meta = list(_fields(dict(_fields(v))[2]))
            name = bytes(next((w for h, w in meta if h == 2), b"")).decode()
            for h, w in meta:
                stat = dict(_fields(w)) if h == 5 else {}   # .stats
                if stat_names.get(stat.get(1)) == b"Hlo Proto" and 6 in stat:
                    out[name] = _hlo_op_names(stat[6])
    return out


def _hlo_op_names(hlo_proto) -> Dict[str, str]:
    out = {}
    module = dict(_fields(hlo_proto)).get(1)         # HloProto.hlo_module
    for f, comp in _fields(module) if module is not None else ():
        if f != 3:                                   # .computations
            continue
        for g, ins in _fields(comp):
            if g != 2:                               # .instructions
                continue
            fields = dict(_fields(ins))
            meta = dict(_fields(fields[7])) if 7 in fields else {}
            if 1 in fields and 2 in meta:            # name; op_name
                out[bytes(fields[1]).decode()] = bytes(meta[2]).decode()
    return out


def _fields(buf):
    """(field number, value) of each field of one protobuf message:
    an int for varint and fixed-width fields, a memoryview of the bytes
    for length-delimited ones."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = int.from_bytes(buf[i:i + n], "little"), i + n
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _varint(buf, i):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return x, i


def leaves(ops: Sequence[Op]) -> List[Op]:
    """Ops that hold no other op: drop loops and calls by name, and any
    op whose interval holds the next op's start."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    out = []
    for i, o in enumerate(ops):
        if ENCLOSING.match(o[0]):
            continue
        if i + 1 < len(ops) and ops[i + 1][1] < o[2] and ops[i + 1][2] <= o[2]:
            continue
        out.append(o)
    return out


def timeline(spans: Sequence[Tuple[str, float, float]], lo: float,
             hi: float) -> List[Tuple[float, float, Optional[str], bool]]:
    """[lo, hi) cut where any span starts or ends: (start, end, the
    innermost span open there or None, whether an ``engine.round`` is
    open).  Innermost is the open span that started last."""
    cuts = sorted({lo, hi} | {t for _, s, e in spans for t in (s, e)
                              if lo < t < hi})
    by_start = sorted(spans, key=lambda x: x[1])
    out, open_, k = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(by_start) and by_start[k][1] <= a:
            open_.append(by_start[k])
            k += 1
        open_ = [sp for sp in open_ if sp[2] > a]
        inner = max(open_, key=lambda sp: (sp[1], -sp[2]), default=None)
        out.append((a, b, inner[0] if inner else None,
                    any(sp[0] == ROUND for sp in open_)))
    return out


@dataclass
class Program:
    window_s: float
    # (engine.round span, device-busy time inside it) per round, s
    rounds: List[Tuple[float, float]] = field(default_factory=list)
    idle_in_round_s: float = 0.0          # averaged over chips
    idle_outside_round_s: float = 0.0
    # idle s by the innermost program span open ("outside" when none)
    idle_by_span: Dict[str, float] = field(default_factory=dict)
    # durations (s) of each jitted program's launches in the window
    launches: Dict[str, List[float]] = field(default_factory=dict)
    # leaf-op device s by scope inside each program's launches
    # ("" for ops that carry a path but none of the scopes asked for;
    # "(none)" for ops with no path at all)
    scope_s: Dict[str, Dict[str, float]] = field(default_factory=dict)
    # the longest idle gaps, named by the innermost program span open
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)


SCOPES = ("attention", "mlp", "logits", "kv_scatter")


def _scope_key(path: str) -> str:
    if not path:
        return "(none)"
    parts = path.split("/")
    return next((s for s in SCOPES if s in parts), "")


def summarise(tr: Dict, top: int = 10) -> Optional[Program]:
    """Reduce a loaded trace; None if it holds no device op or no
    window marks (as ``bench.core.trace.summarise``)."""
    marks = sorted(s for n, s, _ in tr["host"] if n == "bench.mark")
    devs = [d for d in tr["devices"].values() if d["ops"]]
    if len(marks) < 2 or not devs:
        return None
    lo, hi = marks[0], marks[-1]
    spans = [(n, s, e) for n, s, e in tr["host"] if n.startswith(PROGRAM)]
    cuts = timeline(spans, lo, hi)
    in_round = union((a, b) for a, b, _, r in cuts if r)
    p = Program(window_s=(hi - lo) * 1e-9)
    idle_by: Dict[str, float] = defaultdict(float)
    launches: Dict[str, List[float]] = defaultdict(list)
    scope_s: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    busy0: List[Tuple[float, float]] = []
    gaps0: List[Tuple[float, float]] = []
    for k, d in enumerate(devs):
        busy = union((max(s, lo), min(e, hi)) for _, s, e, _ in d["ops"]
                     if e > lo and s < hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        if k == 0:
            busy0, gaps0 = busy, idle
        inside = length(intersect(idle, in_round))
        p.idle_in_round_s += inside * 1e-9 / len(devs)
        p.idle_outside_round_s += (length(idle) - inside) * 1e-9 / len(devs)
        for (a, b), (_, _, who, _) in _pairs(idle, cuts):
            idle_by[who or "outside"] += (b - a) * 1e-9 / len(devs)
        mods = sorted(((_module_name(n), s, e) for n, s, e in d["modules"]
                       if s >= lo and e <= hi), key=lambda m: m[1])
        ops = leaves(d["ops"])
        j = 0
        for name, s, e in mods:
            launches[name].append((e - s) * 1e-9)
            while j < len(ops) and ops[j][1] < s:
                j += 1
            i = j
            while i < len(ops) and ops[i][1] < e:
                o = ops[i]
                scope_s[name][_scope_key(o[3])] += \
                    (min(o[2], e) - o[1]) * 1e-9
                i += 1
    for n, s, e in sorted(spans, key=lambda x: x[1]):
        if n == ROUND and s >= lo and e <= hi:
            p.rounds.append(((e - s) * 1e-9,
                             length(clip(busy0, s, e)) * 1e-9))
    starts = [a for a, _, _, _ in cuts]
    gaps = []
    for s, e in gaps0:
        who = cuts[bisect.bisect_right(starts, (s + e) / 2) - 1][2]
        gaps.append((who or "other", (e - s) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    p.idle_by_span = dict(idle_by)
    p.launches = dict(launches)
    p.scope_s = {k: dict(v) for k, v in scope_s.items()}
    p.idle_gaps = gaps[:top]
    return p


def _pairs(a, b):
    """Overlapping pieces of two sorted lists of disjoint intervals:
    ((start, end) of the overlap, the item of ``b`` it lies in)."""
    i = j = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            yield (s, e), b[j]
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1


def of(ctx) -> Optional[Program]:
    """The run's ``Program``, read once and kept on ``ctx``: None in an
    untraced run, or when the trace found is not the one ``ctx.trace``
    summarised (its window differs).  The trace is read from
    ``ctx.trace_dir``, where ``bench/run.py`` had the profiler write it."""
    if getattr(ctx, "trace", None) is None:
        return None
    if not hasattr(ctx, "program"):
        try:
            prog = summarise(load(str(ctx.trace_dir)))
        except FileNotFoundError:
            prog = None
        if prog is not None and abs(prog.window_s - ctx.trace.window_s) > 1e-6:
            prog = None
        ctx.program = prog
    return ctx.program


def scope_share(prog: Optional[Program], module: str,
                scope: str) -> Optional[float]:
    """Leaf-op device time under ``scope`` inside ``module``'s launches,
    over those launches' time, in percent; None if the module did not
    run or none of its ops carries a scope path."""
    if prog is None or not prog.launches.get(module):
        return None
    by = prog.scope_s.get(module, {})
    if not sum(v for k, v in by.items() if k != "(none)"):
        return None
    return 100.0 * by.get(scope, 0.0) / sum(prog.launches[module])


def mean_launch_ms(prog: Optional[Program], module: str) -> Optional[float]:
    ts = prog.launches.get(module) if prog is not None else None
    return 1e3 * sum(ts) / len(ts) if ts else None


def _dump_ops(log_dir: str, n: int) -> Dict:
    """The first ``n`` ops of each program in the trace with the scope
    path found for each, and the programs whose HLO the trace holds: to
    check the scope lookup by hand."""
    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    tr = load(log_dir)
    out: Dict[str, object] = {"hlo_programs": sorted(op_names(path))}
    for plane, d in sorted(tr["devices"].items())[:1]:
        mods = sorted(d["modules"], key=lambda m: m[1])
        seen: Dict[str, list] = defaultdict(list)
        for name, s, e, scope in d["ops"]:
            m = next((x for x, a, b in mods if a <= s < b), None)
            if m and len(seen[m]) < n:
                seen[m].append([name[:120], scope])
        out[plane] = dict(seen)
    return out


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("log_dir")
    ap.add_argument("--ops", type=int, default=0,
                    help="print this many ops of each program, with the "
                         "scope path found for each")
    args = ap.parse_args(argv)
    if args.ops:
        print(json.dumps(_dump_ops(args.log_dir, args.ops), indent=1))
        return 0
    tr = load(args.log_dir)
    prog = [(s, e) for n, s, e in tr["host"] if n.startswith(PROGRAM)]
    if prog and not any(n == "bench.mark" for n, _, _ in tr["host"]):
        # a profile taken outside the benchmark: its window is the
        # extent of the program's spans
        tr["host"] += [("bench.mark", min(prog)[0], min(prog)[0]),
                       ("bench.mark", max(e for _, e in prog),
                        max(e for _, e in prog))]
    p = summarise(tr)
    if p is None:
        print("no window marks or no device ops in the trace",
              file=sys.stderr)
        return 1
    marks = sorted(s for n, s, _ in tr["host"] if n == "bench.mark")
    count: Dict[str, int] = defaultdict(int)
    for n, s, _ in tr["host"]:
        if n.startswith(PROGRAM) and marks[0] <= s < marks[-1]:
            count[n] += 1
    busy_rounds = sum(b for _, b in p.rounds)
    print(json.dumps({
        "window_s": p.window_s, "rounds": len(p.rounds),
        "spans": dict(sorted(count.items())),
        "round_ms": 1e3 * sum(s for s, _ in p.rounds) / max(len(p.rounds), 1),
        "round_busy_ms": 1e3 * busy_rounds / max(len(p.rounds), 1),
        "idle_in_round_s": p.idle_in_round_s,
        "idle_outside_round_s": p.idle_outside_round_s,
        "idle_by_span_s": dict(sorted(p.idle_by_span.items(),
                                      key=lambda x: -x[1])),
        "launches": {k: [len(v), 1e3 * sum(v) / len(v)]
                     for k, v in sorted(p.launches.items())},
        "scope_share": {k: {s: 100.0 * t / sum(p.launches[k])
                            for s, t in sorted(v.items())}
                        for k, v in sorted(p.scope_s.items())},
        "idle_gaps": p.idle_gaps}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
