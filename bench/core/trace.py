"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` the JAX profiler wrote into plain
tuples; ``summarise`` does the arithmetic on those tuples only, so a
small recorded trace (``bench/tests/data``) checks it on the CPU.

Device time comes from each TPU's ``XLA Ops`` line: busy time is the
union of the intervals in which an op ran, idle is the rest of the
traced window.  Host spans are the benchmark's own
``jax.profiler.TraceAnnotation`` names (``bench.mark``, ``round.*``,
``admit``, ``generator``, ``pim_flush``), on the same clock.
"""

from __future__ import annotations

import glob
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Span = Tuple[str, float, float]          # (name, start_ns, end_ns)

HOST_SPANS = ("bench.mark", "round.prefill", "round.decode", "admit",
              "generator", "pim_flush")
# idle gaps are put down to the innermost of these spans open at the gap
GAP_ORDER = ("pim_flush", "admit", "generator", "round.prefill",
             "round.decode")
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all", re.I)


def load(log_dir: str) -> Dict:
    """{"devices": {plane: {"ops": [...], "modules": [...]}},
    "host": [...]} from the newest trace under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no trace under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices: Dict[str, Dict[str, List[Span]]] = {}
    host: List[Span] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            ops = lines.get("XLA Ops")
            mods = lines.get("XLA Modules")
            devices[plane.name] = {
                "ops": [(e.name, e.start_ns, e.end_ns)
                        for e in (ops.events if ops else [])],
                "modules": [(e.name, e.start_ns, e.end_ns)
                            for e in (mods.events if mods else [])]}
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                host += [(e.name, e.start_ns, e.end_ns) for e in ln.events
                         if e.name in HOST_SPANS]
    return {"devices": devices, "host": host}


def union(iv: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(iv: Sequence[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in iv)


def clip(iv: Sequence[Tuple[float, float]], lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def intersect(a: Sequence[Tuple[float, float]],
              b: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Intersection of two unions (each sorted and disjoint)."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


@dataclass
class Summary:
    window_s: float
    busy_s: float                         # averaged over chips
    rounds: List[Tuple[str, float, float]] = field(default_factory=list)
    module_s: Dict[str, float] = field(default_factory=dict)
    collective_s: float = 0.0
    collective_exposed_s: float = 0.0
    device_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)


def summarise(tr: Dict, top: int = 10) -> Optional[Summary]:
    """Reduce a loaded trace; None if it holds no device op or no
    window marks."""
    marks = sorted(s for n, s, _ in tr["host"] if n == "bench.mark")
    devs = [d for d in tr["devices"].values() if d["ops"]]
    if len(marks) < 2 or not devs:
        return None
    lo, hi = marks[0], marks[-1]
    win = hi - lo
    busy_each, op_time = [], defaultdict(float)
    mod_time: Dict[str, float] = defaultdict(float)
    coll = coll_exposed = 0.0
    busy0: List[Tuple[float, float]] = []
    for k, d in enumerate(devs):
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in d["ops"]
               if e > lo and s < hi]
        busy = union((s, e) for _, s, e in ops)
        if k == 0:
            busy0 = busy
        busy_each.append(length(busy))
        for n, s, e in ops:
            op_time[n] += (e - s) / len(devs)
        for n, s, e in clip_named(d["modules"], lo, hi):
            mod_time[_module_name(n)] += (e - s) / len(devs)
        c = union((s, e) for n, s, e in ops if COLLECTIVE.search(n))
        other = union((s, e) for n, s, e in ops if not COLLECTIVE.search(n))
        coll += length(c) / len(devs)
        coll_exposed += (length(c) - length(intersect(c, other))) / len(devs)
    rounds = []
    for n, s, e in sorted(tr["host"], key=lambda x: x[1]):
        if n.startswith("round.") and s >= lo and e <= hi:
            rounds.append((n, (e - s) * 1e-9,
                           length(clip(busy0, s, e)) * 1e-9))
    gaps = []
    edges = [lo] + [x for iv in busy0 for x in iv] + [hi]
    spans = [(n, s, e) for n, s, e in tr["host"] if n in GAP_ORDER]
    for s, e in zip(edges[::2], edges[1::2]):
        if e > s:
            mid = (s + e) / 2
            open_ = {n for n, a, b in spans if a <= mid < b}
            who = next((n for n in GAP_ORDER if n in open_), "other")
            gaps.append((who, (e - s) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    ops_top = sorted(op_time.items(), key=lambda x: -x[1])[:top]
    return Summary(
        window_s=win * 1e-9,
        busy_s=sum(busy_each) / len(busy_each) * 1e-9,
        rounds=rounds,
        module_s={k: v * 1e-9 for k, v in mod_time.items()},
        collective_s=coll * 1e-9, collective_exposed_s=coll_exposed * 1e-9,
        device_ops=[(n, t * 1e-9) for n, t in ops_top],
        idle_gaps=gaps[:top])


def clip_named(ev: Sequence[Span], lo: float, hi: float) -> List[Span]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in ev if e > lo and s < hi]
