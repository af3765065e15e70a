#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

  python bench/run.py --workload granite-3-8b.chat-prefix --seed 7 \
      --seconds 30 --trace 0

Everything about a cell is data: ``BENCHMARK.json`` names its
configuration (``bench/configs/<config>.json``) and traffic mix
(``bench/traffic/<traffic>.json``), the configuration names its
architecture module (``"reference"``, bench/core/arch.py), and each
metric is read by ``bench/metrics/<metric>.py``.  The run draws the
weights on the chips from the seed, builds the engine the
configuration's deployment describes, warms every program the traffic
can reach, serves the traffic (a lead-in, then the measured window),
then checks the served tokens against the module's plain float32
reference.  With ``--trace 1`` part of the window is profiled and the
per-layer metrics are reported instead of the end-to-end ones.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``; the numbers compared for ``correct`` come last, under
``compared``).  With no TPU, or fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.core.arch import Refused  # noqa: E402


def load_cell(name: str) -> dict:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    cfg = json.loads((ROOT / conf["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return {"cell": cell, "cfg": cfg, "mix": mix,
            "end_to_end": mine(spec["end_to_end"]),
            "per_layer": mine(spec["per_layer"])}


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    return _load(BENCH / "metrics" / f"{metric}.py",
                 f"bench_metric_{metric}").read


def architecture(cfg: dict):
    """The configuration's architecture module: the file it names under
    ``"reference"``, loaded by path (once a process, so its jitted
    functions keep their compiled programs)."""
    return _architecture(ROOT / cfg["reference"])


@functools.lru_cache(maxsize=None)
def _architecture(path: Path):
    return _load(path, f"bench_arch_{path.stem}")


class CompileClock:
    """Seconds and count of backend compiles, from JAX's own events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds, self.count = 0.0, 0

        def listen(event, duration, **_):
            if event == self.EVENT:
                self.seconds += duration
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


def build(arch, cfg: dict, dep: dict, seed: int):
    """Weights drawn on the chips (each chip its own shard), and the
    engine the deployment describes."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.distributed.sharding import sharding_env
    from repro.launch.mesh import make_local_mesh
    from repro.models import params as P_mod
    from repro.models import transformer as T
    from repro.serving.engine import PagedEngine

    from bench.core import arenas, weights

    mcfg = arch.model_config(cfg)
    defs = T.model_defs(mcfg)
    mesh = None
    if dep["mesh_model"] > 1:
        mesh = make_local_mesh(model=dep["mesh_model"])
        with sharding_env(mesh, fsdp=False):
            specs = P_mod.param_specs(defs)
        shard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda s: isinstance(s, PartitionSpec))
    else:
        shard = None

    def draw(seed):
        return weights.draw_tree(defs, seed, cfg["hidden_size"],
                                 arch.leaf_std, out_shardings=shard)

    params = draw(seed)
    eng = PagedEngine(mcfg, params, page_size=dep["page_size"],
                      num_pages=dep["num_pages"], seed=seed % (1 << 31),
                      prefix_cache=dep["prefix_cache"],
                      max_prefill_chunk=dep["max_prefill_chunk"],
                      mixed_rounds=dep["mixed_rounds"], mesh=mesh)
    jax.block_until_ready((eng.params, arenas.arrays(eng.cache)))
    return eng, draw


def sweep(eng, c: dict, args, clock, log) -> int:
    """Serve the chat mix at each rate in turn on one engine, and log
    whether the due-but-unfinished backlog grows across the window.
    Between rates the engine finishes what it holds and the prefix
    cache is emptied."""
    from bench.core import drive, readers, traffic
    mix, dep = c["mix"], c["cfg"]["deployment"]
    for rate in (float(x) for x in args.sweep.split(",")):
        mix["rate_rps"] = rate
        reqs = traffic.sessions(mix, args.seed, mix["lead_in_s"]
                                + args.seconds, c["cfg"]["vocab_size"])
        records, win = drive.open_loop(
            eng, dep.get("server", {}), reqs, mix["lead_in_s"],
            args.seconds, drive.Tracer(None, 0, eng), clock)
        ctx = SimpleNamespace(records=records, win=win, seconds=args.seconds)
        log(phase="sweep", rate_rps=rate,
            due=len(readers.due_in_window(ctx)),
            backlog_slope_per_s=win.backlog_slope(),
            backlog_end=win.backlog[-1][1] if win.backlog else None,
            ttft_p90_ms=reader("ttft_p90_ms")(ctx),
            itl_p99_ms=reader("itl_p99_ms.chat")(ctx),
            max_batch=win.max_batch, max_pages=win.max_pages,
            compiles=win.compiles)
        eng.run(max_rounds=1 << 30)
        eng.cache.clear_prefix()
    return 0


def _serve(eng, c: dict, seed: int, seconds: float, tracer, clock):
    from bench.core import drive, traffic
    mix, dep = c["mix"], c["cfg"]["deployment"]
    lead, vocab = mix["lead_in_s"], c["cfg"]["vocab_size"]
    if mix["arrival"] == "open_sessions":
        reqs = traffic.sessions(mix, seed, lead + seconds, vocab)
        return drive.open_loop(eng, dep.get("server", {}), reqs, lead,
                               seconds, tracer, clock)
    pool = traffic.ClosedPool(mix, seed, vocab)
    return drive.closed_loop(eng, pool, mix["concurrency"],
                             mix["fill_per_round"], lead, seconds, tracer,
                             clock)


def check_seeds(eng, draw, arch, c: dict, args, clock, log) -> int:
    """For each seed: new weights, the cell's traffic for a short window
    on the same engine, then the served tokens (and with ``--control``
    the module's control) against the reference, with the engine's
    weights and arenas freed meanwhile."""
    from bench.core import arenas, check, drive
    cfg, ck = c["cfg"], c["cfg"]["check"]
    shapes = arenas.layout(eng.cache)
    for i, seed in enumerate(int(x) for x in args.seeds.split(",")):
        if i:
            eng.params = draw(seed)
            arenas.rebuild(eng.cache, shapes)
        records, win = _serve(eng, c, seed, args.seconds,
                              drive.Tracer(None, 0, eng), clock)
        eng.run(max_rounds=1 << 30)
        if eng.cache.prefix is not None:
            eng.cache.clear_prefix()
        eng.params = None
        arenas.free(eng.cache)
        gc.collect()
        picked = check.sample(records, seed, ck["min_tokens"],
                              ck["max_seqs"])
        out = {"phase": "seed", "seed": seed, "finished": len(picked),
               "window_compiles": win.compiles, "due": len(records),
               "budgets_met": check.budgets_met(picked),
               "sampled": [[len(r.prompt), r.cached, len(r.tokens)]
                           for r in picked]}
        if picked:
            out["program"] = check.served_gap(arch, cfg, seed, picked)
            if args.control:
                out["control"] = check.control_gaps(arch, cfg, seed,
                                                    picked)
        log(**out)
    return 0


class GcPauses:
    """Python's garbage-collector pauses while the traffic runs, by
    generation: tells them apart from other host stalls in the token
    gaps."""

    def __init__(self):
        self.t0, self.ms = 0.0, {0: [], 1: [], 2: []}
        gc.callbacks.append(self.note)

    def note(self, phase, info):
        if phase == "start":
            self.t0 = time.perf_counter()
        else:
            self.ms[info["generation"]].append(
                1e3 * (time.perf_counter() - self.t0))

    def summary(self) -> dict:
        return {f"gen{g}": [len(v), round(sum(v), 3),
                            round(max(v, default=0.0), 3)]
                for g, v in self.ms.items()}


def gap_counts(records, win) -> dict:
    """How many token gaps closing in the window reach each length (ms):
    where the ITL tail sits."""
    from bench.core.readers import window_gaps
    gaps = np.asarray(window_gaps(SimpleNamespace(records=records, win=win)))
    edges = (0, 50, 100, 120, 130, 140, 160, 200, 300, 500, 1000)
    return {"n": int(gaps.size), **{f">={e}": int((gaps >= e).sum())
                                    for e in edges[1:]}}


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated arrival rates: find the knee "
                         "(one engine, one window per rate; no result "
                         "line)")
    ap.add_argument("--seeds", default=None,
                    help="comma-separated seeds: read the compared numbers "
                         "of each on one engine (setting the limits; no "
                         "result line)")
    ap.add_argument("--control", action="store_true",
                    help="score the architecture module's control (the "
                         "reference one precision step lower) in the "
                         "program's place (it must read correct: false); "
                         "with --seeds, read it beside the program")
    return ap


def main(argv=None) -> int:
    args = parser().parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the program (src/repro) is not in this checkout; "
              "nothing run", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        return run(args, load_cell(args.workload))
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2


def run(args, c: dict, require_tpu: bool = True) -> int:
    """One run of the cell ``c`` (``load_cell``).  ``require_tpu=False``
    is for the CPU tests that drive a tiny cell end to end."""
    cfg, mix, dep = c["cfg"], c["mix"], c["cfg"]["deployment"]
    chips = c["cell"]["chips"]

    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise Refused(f"no TPU (JAX found {devs[0].platform}); nothing run")
    if len(devs) < chips:
        raise Refused(f"{args.workload} needs {chips} chips, found "
                      f"{len(devs)}")
    from repro.launch.compile_cache import enable_compile_cache

    from bench.core import arenas, check, drive, peaks, trace, warmup

    if require_tpu:
        enable_compile_cache()
    # cache every program, however quick to compile: a run must find all
    # of them there after the first
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    peak = (peaks.peaks(devs[0].device_kind) if require_tpu
            else c["peak"])
    used = devs[:chips]
    clock = CompileClock()

    def log(**kw):
        print(json.dumps(kw), file=sys.stderr, flush=True)

    t = time.perf_counter()
    arch = architecture(cfg)
    eng, draw = build(arch, cfg, dep, args.seed)
    held = arenas.describe(eng.cache)
    log(phase="build", seconds=time.perf_counter() - t,
        compile_s=clock.seconds, programs=clock.count,
        state_slots=(eng.cache.state.num_slots
                     if eng.cache.state is not None else None),
        arena_bytes=sum(a["bytes"] for a in held), arenas=held)
    t, c0, n0 = time.perf_counter(), clock.seconds, clock.count
    shapes = warmup.plan(dep, mix)
    n_prog = warmup.run(eng, shapes)
    log(phase="warmup", seconds=time.perf_counter() - t, programs=n_prog,
        compile_s=clock.seconds - c0, compiled=clock.count - n0,
        **{k: len(v) for k, v in shapes.items()})

    secs = args.seconds
    if args.sweep:
        return sweep(eng, c, args, clock, log)
    if args.seeds:
        return check_seeds(eng, draw, arch, c, args, clock, log)
    log_dir = None
    if args.trace:
        log_dir = str(c.get("out", ROOT / "bench_out") / "trace")
        shutil.rmtree(log_dir, ignore_errors=True)
    tracer = drive.Tracer(log_dir, min(secs, 6.0), eng)
    setup_s = time.perf_counter() - T_START
    pauses = GcPauses()
    try:
        records, win = _serve(eng, c, args.seed, secs, tracer, clock)
    finally:
        gc.callbacks.remove(pauses.note)
    stats = [d.memory_stats() for d in used]
    mem_peak = (max(m["peak_bytes_in_use"] for m in stats)
                if all(stats) else None)
    due = [r for r in records if win.start_ms <= r.due_ms < win.end_ms]
    log(phase="window", requests=len(records), due=len(due),
        memory_peak_bytes=mem_peak,
        bytes_limit=stats[0].get("bytes_limit") if stats[0] else None,
        rounds=win.counters1["engine.decode_rounds"]
        - win.counters0["engine.decode_rounds"],
        max_batch=win.max_batch, max_pages=win.max_pages,
        compiles=win.compiles, generator_late_p99_ms=(sorted(win.gen_late_ms)[
            int(0.99 * (len(win.gen_late_ms) - 1))]
            if win.gen_late_ms else None),
        gc_pauses=pauses.summary(), token_gaps=gap_counts(records, win))
    if win.compiles:
        raise Refused(
            f"{win.compiles} programs compiled inside the window (largest "
            f"batch {win.max_batch}, largest page ops {win.max_pages}; "
            f"warm-up covers batches to {dep['max_batch']} and page inits "
            f"to {dep['max_page_init']}): this traffic passes the warm-up "
            f"plan, so the window measured compiles")
    # free the program's weights and arenas before the reference runs
    eng.params = None
    arenas.free(eng.cache)
    del eng, tracer.eng
    gc.collect()

    # correctness, on the finished requests, with the engine gone
    t = time.perf_counter()
    ck = cfg["check"]
    picked = check.sample(records, args.seed, ck["min_tokens"],
                          ck["max_seqs"])
    # with --control the tokens of the module's control stand in for the
    # served ones, through the same comparison and limit
    score = check.control_gaps if args.control else check.served_gap
    gap = score(arch, cfg, args.seed, picked) if picked else None
    compared = {}
    if gap is not None:
        compared["max_logit_gap"] = {"value": gap["max_logit_gap"],
                                     "limit": ck["max_logit_gap"],
                                     "tokens": gap["tokens"]}
    compared["budgets_met"] = {"value": int(check.budgets_met(picked)),
                               "limit": 1}
    compared["sampled_requests"] = {"value": len(picked), "limit": 1}
    ok = (gap is not None and len(picked) >= 1 and check.budgets_met(picked)
          and ck["max_logit_gap"] is not None
          and gap["max_logit_gap"] <= ck["max_logit_gap"])
    log(phase="check", seconds=time.perf_counter() - t,
        sampled=[[len(r.prompt), r.cached, len(r.tokens)] for r in picked])

    summary = None
    if args.trace:
        summary = trace.summarise(trace.load(log_dir))
    ctx = SimpleNamespace(cfg=cfg, arch=arch, dep=dep, mix=mix, chips=chips,
                          peak=peak, setup_s=setup_s, seconds=secs,
                          records=records, win=win, trace=summary,
                          trace_dir=log_dir)
    metrics = {}
    for m in (c["per_layer"] if args.trace else c["end_to_end"]):
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": mem_peak}
    out = {"correct": bool(ok), "attempted": len(due),
           "failed": sum(r.rejected for r in due), "metrics": metrics,
           "device": device}
    if summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        out["breakdown"] = {"device_ops": summary.device_ops,
                            "idle_gaps": summary.idle_gaps}
    out["compared"] = compared
    for k, v in compared.items():
        print(f"compared {k}: {v['value']} limit {v['limit']}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
