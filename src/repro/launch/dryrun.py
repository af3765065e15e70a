"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract memory/cost/collective evidence.

The production meshes are 512 host-platform (CPU) devices, set up by
:func:`use_host_devices` before anything initialises a JAX backend
(jax locks the device count at first init, not at import).

Usage:
  python -m repro.launch.dryrun --arch granite-3-8b --shape train_4k
  python -m repro.launch.dryrun --arch granite-3-8b --shape decode_32k --multi-pod
  python -m repro.launch.dryrun --all          # subprocess sweep driver

Artifacts: experiments/dryrun/<arch>__<shape>__<mesh>.json
"""

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, ParallelConfig, OptimizerConfig, cells_for
from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed.sharding import resolve_spec, sharding_env
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.models.params import abstract_params, param_count, param_specs
from repro.roofline import analysis as ra
from repro.roofline import hw
from repro.training import train_step as ts

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "../../../experiments/dryrun")

# Per-arch parallel overrides for the production dry-run (big configs use
# bf16 masters + bf16 optimizer moments and more microbatches; see
# EXPERIMENTS.md §Dry-run notes).
PARALLEL_OVERRIDES: Dict[str, Dict[str, Any]] = {
    "jamba-1.5-large-398b": dict(param_dtype="bfloat16", opt_state_dtype="bfloat16",
                                 microbatches=8),
    "deepseek-v2-236b": dict(param_dtype="bfloat16", opt_state_dtype="bfloat16",
                             microbatches=8),
    "llava-next-34b": dict(param_dtype="bfloat16", opt_state_dtype="bfloat16",
                           microbatches=8),
    "llama4-scout-17b-a16e": dict(microbatches=8),
    "granite-3-8b": dict(microbatches=4),
    "minitron-8b": dict(microbatches=2),
}


def parallel_for(cfg: ModelConfig, multi_pod: bool, **overrides) -> ParallelConfig:
    kw: Dict[str, Any] = dict(multi_pod=multi_pod, remat="full",
                              attention_impl="chunked", moe_impl="shard_map")
    kw.update(PARALLEL_OVERRIDES.get(cfg.name, {}))
    kw.update(overrides)
    return ParallelConfig(**kw)


# --------------------------------------------------------------------- #
# input_specs — ShapeDtypeStruct stand-ins for every model input
# --------------------------------------------------------------------- #


def enc_dec_split(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[int, int]:
    """(enc_len, dec_len) per DESIGN.md SS6."""
    if shape.kind == "train":
        return shape.seq_len // 2, shape.seq_len // 2
    if shape.kind == "prefill":
        return 4096, shape.seq_len - 4096
    return 4096, shape.seq_len  # decode: dec KV budget = seq_len


def input_specs(cfg: ModelConfig, shape: ShapeConfig,
                pcfg: ParallelConfig) -> Dict[str, Any]:
    """Abstract batch for the step function (no device allocation)."""
    B, S = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    fd = cfg.frontend_dim or cfg.d_model

    if shape.kind == "train":
        if cfg.family == "encdec":
            e, d = enc_dec_split(cfg, shape)
            return {"tokens": sds((B, d), jnp.int32),
                    "labels": sds((B, d), jnp.int32),
                    "frames": sds((B, e, fd), jnp.float32)}
        if cfg.family == "vlm":
            return {"tokens": sds((B, S - cfg.num_patch_tokens), jnp.int32),
                    "labels": sds((B, S), jnp.int32),
                    "patch_embeds": sds((B, cfg.num_patch_tokens, fd), jnp.float32)}
        return {"tokens": sds((B, S), jnp.int32),
                "labels": sds((B, S), jnp.int32)}

    if shape.kind == "prefill":
        if cfg.family == "encdec":
            e, d = enc_dec_split(cfg, shape)
            return {"tokens": sds((B, d), jnp.int32),
                    "frames": sds((B, e, fd), jnp.float32)}
        if cfg.family == "vlm":
            return {"tokens": sds((B, S - cfg.num_patch_tokens), jnp.int32),
                    "patch_embeds": sds((B, cfg.num_patch_tokens, fd), jnp.float32)}
        return {"tokens": sds((B, S), jnp.int32)}

    # decode
    return {"tokens": sds((B, 1), jnp.int32)}


def batch_pspecs(cfg: ModelConfig, batch: Dict[str, Any]) -> Dict[str, P]:
    return {k: resolve_spec(v.shape, ("batch",) + (None,) * (len(v.shape) - 1))
            for k, v in batch.items()}


# --------------------------------------------------------------------- #
# Cell lowering
# --------------------------------------------------------------------- #


def _ns(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def lower_cell(cfg: ModelConfig, shape: ShapeConfig, *, multi_pod: bool,
               parallel_overrides: Optional[Dict[str, Any]] = None):
    """Build mesh + abstract inputs, lower and compile the step. Returns
    (compiled, lowered, info_dict)."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    pcfg = parallel_for(cfg, multi_pod, **(parallel_overrides or {}))
    pdtype = jnp.bfloat16 if pcfg.param_dtype == "bfloat16" else jnp.float32
    sdtype = jnp.bfloat16 if pcfg.opt_state_dtype == "bfloat16" else jnp.float32

    from repro.distributed.sharding import default_rules
    rules = default_rules(multi_pod)
    if pcfg.row_parallel_attn:
        rules["dmodel_rp"] = ("model",)
    with sharding_env(mesh, multi_pod=multi_pod, fsdp=pcfg.fsdp, rules=rules):
        defs = T.model_defs(cfg)
        pspecs = param_specs(defs)
        n_params = param_count(defs)
        batch = input_specs(cfg, shape, pcfg)
        bspecs = batch_pspecs(cfg, batch)

        if shape.kind == "train":
            params_abs = abstract_params(defs, pdtype)
            init_state, step = ts.make_train_step(
                cfg, pcfg, OptimizerConfig(), state_dtype=sdtype)
            opt_specs = {"m": pspecs, "v": pspecs, "step": P()}
            state_abs = {
                "params": params_abs,
                "opt": {"m": abstract_params(defs, sdtype),
                        "v": abstract_params(defs, sdtype),
                        "step": jax.ShapeDtypeStruct((), jnp.int32)},
            }
            state_specs = {"params": pspecs, "opt": opt_specs}
            jf = jax.jit(step,
                         in_shardings=(_ns(mesh, state_specs), _ns(mesh, bspecs)),
                         out_shardings=(_ns(mesh, state_specs), None),
                         donate_argnums=(0,))
            lowered = jf.lower(state_abs, batch)
        else:
            params_abs = abstract_params(defs, jnp.bfloat16)  # serving: bf16
            B = shape.global_batch
            enc_len = enc_dec_split(cfg, shape)[0] if cfg.family == "encdec" else 0
            max_len = shape.seq_len
            kvd = jnp.float8_e4m3fn if pcfg.kv_cache_dtype.startswith("float8") \
                else None
            cache_abs = T.cache_spec(cfg, B, max_len, enc_len, kv_dtype=kvd)
            cspecs = T.cache_pspecs(cfg, B, max_len, enc_len)
            lens = jax.ShapeDtypeStruct((B,), jnp.int32)
            lens_spec = resolve_spec((B,), ("batch",))
            if shape.kind == "prefill":
                step = ts.make_prefill_step(cfg, pcfg)
                jf = jax.jit(step,
                             in_shardings=(_ns(mesh, pspecs), _ns(mesh, bspecs),
                                           _ns(mesh, cspecs),
                                           NamedSharding(mesh, lens_spec)),
                             out_shardings=(None, _ns(mesh, cspecs)),
                             donate_argnums=(2,))
                lowered = jf.lower(params_abs, batch, cache_abs, lens)
            else:
                step = ts.make_decode_step(cfg, pcfg)
                wpos = jax.ShapeDtypeStruct((), jnp.int32)
                jf = jax.jit(step,
                             in_shardings=(_ns(mesh, pspecs), _ns(mesh, bspecs),
                                           _ns(mesh, cspecs),
                                           NamedSharding(mesh, P()),
                                           NamedSharding(mesh, lens_spec)),
                             out_shardings=(None, _ns(mesh, cspecs)),
                             donate_argnums=(2,))
                lowered = jf.lower(params_abs, batch, cache_abs, wpos, lens)

        t0 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t0

    info = {"arch": cfg.name, "shape": shape.name,
            "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
            "params": n_params, "compile_s": compile_s,
            "param_dtype": pcfg.param_dtype,
            "opt_state_dtype": pcfg.opt_state_dtype,
            "microbatches": pcfg.microbatches}
    return compiled, lowered, info


def analytic_memory(cfg: ModelConfig, shape: ShapeConfig, info: Dict[str, Any]) -> int:
    """First-principles per-device HBM estimate (TPU dtype semantics).

    train: params + grads + 2 opt moments (all sharded over every chip)
           + remat carry stacks + working set allowance.
    serve: bf16 params + KV cache (batch x seq sharded) + activations.
    """
    chips = info["chips"]
    n = info["params"]
    pbytes = 2 if info["param_dtype"] == "bfloat16" else 4
    sbytes = 2 if info["opt_state_dtype"] == "bfloat16" else 4
    B, S = shape.global_batch, shape.seq_len
    d = cfg.d_model
    data_shards = chips // 16  # model axis = 16 on both meshes
    b_local = max(B // data_shards, 1)
    mb = max(info.get("microbatches", 1), 1)

    if shape.kind == "train":
        states = n * (pbytes + 4 + 2 * sbytes) / chips  # +grads fp32
        carry = cfg.num_layers * (b_local // mb) * S * d * 2  # bf16 stacks
        work = 4 * (b_local // mb) * S * d * 4  # a few fp32 working copies
        return int(states + carry + work)

    # serving: bf16 params + cache + small activations
    params_b = n * 2 / chips
    hd = cfg.resolved_head_dim
    if cfg.mla:
        per_tok = cfg.mla.kv_lora_rank + cfg.mla.rope_head_dim
        layers = cfg.num_layers
    elif cfg.family == "ssm":
        per_tok, layers = 0, 0
    elif cfg.family == "hybrid":
        per_tok = 2 * cfg.num_kv_heads * hd
        layers = cfg.num_layers // cfg.attn_every
    elif cfg.family == "encdec":
        per_tok = 2 * cfg.num_kv_heads * hd
        layers = cfg.dec_layers
    else:
        per_tok = 2 * cfg.num_kv_heads * hd
        layers = cfg.num_layers
    cache = layers * B * S * per_tok * 2 / chips  # sharded batch x seq
    if cfg.ssm:
        s_ = cfg.ssm
        d_in = s_.expand * d
        nh = d_in // s_.head_dim
        n_ssm = (cfg.num_layers - cfg.num_layers // cfg.attn_every
                 if cfg.family == "hybrid" else cfg.num_layers)
        cache += n_ssm * B * nh * s_.head_dim * s_.state_dim * 4 / max(data_shards, 1)
    toks = B if shape.kind == "decode" else B * S
    act = 6 * (toks // max(data_shards, 1)) * d * 2
    return int(params_b + cache + act)


def analyze(compiled, lowered, cfg, shape, info) -> Dict[str, Any]:
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = ra.parse_collective_bytes(hlo)
    chips = info["chips"]
    terms = ra.RooflineTerms(
        flops=float(cost.get("flops", 0.0)),
        hbm_bytes=float(cost.get("bytes accessed", 0.0)),
        coll_bytes=float(ra.collective_bytes_total(coll)),
        chips=chips,
        model_flops=ra.model_flops(cfg, shape, info["params"]),
    )
    arg_b = int(mem.argument_size_in_bytes)
    out_b = int(mem.output_size_in_bytes)
    tmp_b = int(mem.temp_size_in_bytes)
    alias_b = int(mem.alias_size_in_bytes)
    peak = arg_b + out_b + tmp_b - alias_b
    analytic = analytic_memory(cfg, shape, info)
    rec = dict(info)
    rec.update({
        "memory": {"argument_bytes": arg_b, "output_bytes": out_b,
                   "temp_bytes": tmp_b, "alias_bytes": alias_b,
                   "peak_bytes_per_device": peak,
                   # CPU backend emulates bf16 in f32 and upcasts whole
                   # saved-residual stacks; TPU keeps bf16. See
                   # EXPERIMENTS.md §Dry-run for the analytic model.
                   "fits_16GiB_hlo_cpu": bool(peak <= hw.HBM_BYTES),
                   "analytic_bytes_per_device": analytic,
                   "fits_16GiB_analytic": bool(analytic <= hw.HBM_BYTES)},
        "collectives": {k: int(v) for k, v in coll.items() if k != "_counts"},
        "collective_counts": coll.get("_counts", {}),
        "roofline": terms.as_dict(),
    })
    return rec


def _cost_scaled_cfgs(cfg: ModelConfig):
    """Two reduced-depth variants (n = uniform-group repeat count) plus
    the full repeat count, for affine cost extrapolation."""
    import dataclasses as dc
    if cfg.family == "hybrid":
        per = cfg.attn_every  # one superblock = `per` sublayers
        return ([(dc.replace(cfg, num_layers=per), 1),
                 (dc.replace(cfg, num_layers=2 * per), 2)],
                cfg.num_layers // per)
    if cfg.family == "encdec":
        return ([(dc.replace(cfg, enc_layers=2, dec_layers=2, num_layers=4), 1),
                 (dc.replace(cfg, enc_layers=4, dec_layers=4, num_layers=8), 2)],
                cfg.enc_layers // 2)
    if cfg.moe and cfg.moe.first_dense_layers:
        fd = cfg.moe.first_dense_layers
        return ([(dc.replace(cfg, num_layers=fd + 2), 2),
                 (dc.replace(cfg, num_layers=fd + 4), 4)],
                cfg.num_layers - fd)
    return ([(dc.replace(cfg, num_layers=2), 2),
             (dc.replace(cfg, num_layers=4), 4)],
            cfg.num_layers)


_COST_KEYS = ("flops_per_chip", "hbm_bytes_per_chip", "collective_bytes_per_chip")


def cost_metrics_extrapolated(cfg: ModelConfig, shape: ShapeConfig,
                              multi_pod: bool,
                              parallel_overrides: Optional[Dict[str, Any]] = None
                              ) -> Dict[str, Any]:
    """Exact affine extrapolation of per-chip cost metrics in layer count.

    Layers within a uniform group are identical, so every additive HLO
    metric (flops, bytes, per-kind collective bytes) is affine in the
    group repeat count n:  m(n) = a + b*n.  Two fully-unrolled reduced
    lowerings (n1 < n2 << n_full) pin (a, b); we report m(n_full).
    """
    (pairs, n_full) = _cost_scaled_cfgs(cfg)
    cost_over = dict(parallel_overrides or {})
    cost_over.update(scan_unroll=True, microbatches=1, attention_chunk=4096)

    # inner SSD chunk-scan: full unroll only when short; otherwise a
    # partial unroll k with a second affine extrapolation in k
    # (cost(L, k) = base + L*(layer_base + k*step) — a while body is
    # counted once, so the counted cost is affine in the unroll factor).
    nc_ssd = 0
    if cfg.ssm is not None and shape.kind in ("train", "prefill"):
        seq = shape.seq_len if shape.kind != "train" else shape.seq_len
        nc_ssd = -(-seq // cfg.ssm.chunk_size)
    use_k_extrap = nc_ssd > 32

    def lower_sample(sub_cfg, k):
        over = dict(cost_over)
        if use_k_extrap:
            over["ssd_unroll"] = k
        c, l, i = lower_cell(sub_cfg, shape, multi_pod=multi_pod,
                             parallel_overrides=over)
        rec = analyze(c, l, sub_cfg, shape, i)
        m = {key: rec["roofline"][key] for key in ("flops_per_chip",
                                                   "hbm_bytes_per_chip",
                                                   "collective_bytes_per_chip")}
        m["collectives"] = rec["collectives"]
        return m, i["compile_s"]

    (cfgA, nA), (cfgB, nB) = pairs
    k1, k2 = 2, 4

    def combine(f):
        """Apply scalar-extrapolation fn over all metrics."""
        keys = ("flops_per_chip", "hbm_bytes_per_chip",
                "collective_bytes_per_chip")
        out = {k: float(f(lambda m: m[k])) for k in keys}
        coll_keys = samples_m[0]["collectives"].keys()
        out["collectives"] = {
            k: int(max(f(lambda m, kk=k: m["collectives"][kk]), 0))
            for k in coll_keys}
        return out

    if not use_k_extrap:
        mA, tA = lower_sample(cfgA, 0)
        mB, tB = lower_sample(cfgB, 0)
        samples_m = [mA, mB]

        def extrap(g):
            a, b = g(mA), g(mB)
            return a + (b - a) / (nB - nA) * (n_full - nA)

        out = combine(extrap)
        out["cost_compile_s"] = tA + tB
        out["extrapolated_from"] = [nA, nB, n_full]
        return out

    # 3-sample scheme: (A, k1), (B, k1), (B, k2) -> extrapolate L and k
    mA1, tA1 = lower_sample(cfgA, k1)
    mB1, tB1 = lower_sample(cfgB, k1)
    mB2, tB2 = lower_sample(cfgB, k2)
    samples_m = [mA1, mB1, mB2]

    def extrap(g):
        step = (g(mB2) - g(mB1)) / (nB * (k2 - k1))      # per-(layer,chunk)
        b_k1 = (g(mB1) - g(mA1)) / (nB - nA)             # per-layer @ k1
        layer_base = b_k1 - k1 * step
        base = g(mA1) - nA * b_k1
        return base + n_full * (layer_base + nc_ssd * step)

    out = combine(extrap)
    out["cost_compile_s"] = tA1 + tB1 + tB2
    out["extrapolated_from"] = [nA, nB, n_full, k1, k2, nc_ssd]
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = ARTIFACT_DIR,
             parallel_overrides: Optional[Dict[str, Any]] = None,
             tag: str = "", cost_pass: Optional[bool] = None) -> Dict[str, Any]:
    """Two measurement paths per cell:

    1. *proof* — full config, production settings (scanned layers,
       chunk 1024, microbatching): memory_analysis + compile evidence.
    2. *cost* — fully-unrolled reduced-depth lowerings, affinely
       extrapolated to full depth (HLO while bodies are otherwise
       counted once).  Single-pod only (the roofline table is 1-pod).
    """
    cfg = ARCHS[arch]
    shape = SHAPES[shape_name]
    compiled, lowered, info = lower_cell(cfg, shape, multi_pod=multi_pod,
                                         parallel_overrides=parallel_overrides)
    rec = analyze(compiled, lowered, cfg, shape, info)
    rec["roofline_scanbody"] = rec.pop("roofline")  # undercounted; kept for reference

    if cost_pass is None:
        cost_pass = not multi_pod
    if cost_pass:
        try:
            ext = cost_metrics_extrapolated(cfg, shape, multi_pod,
                                            parallel_overrides)
            terms = ra.RooflineTerms(
                flops=ext["flops_per_chip"],
                hbm_bytes=ext["hbm_bytes_per_chip"],
                coll_bytes=ext["collective_bytes_per_chip"],
                chips=info["chips"],
                model_flops=ra.model_flops(cfg, shape, info["params"]),
            )
            rec["roofline"] = terms.as_dict()
            rec["collectives"] = ext["collectives"]
            rec["cost_compile_s"] = ext["cost_compile_s"]
            rec["cost_extrapolated_from"] = ext["extrapolated_from"]
        except Exception as e:  # keep proof artifact; flag cost failure
            rec["cost_pass_error"] = repr(e)[:500]

    os.makedirs(out_dir, exist_ok=True)
    name = f"{arch}__{shape_name}__{rec['mesh']}{tag}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


# --------------------------------------------------------------------- #
# Sweep driver (subprocesses: fresh devices per cell, parallelism)
# --------------------------------------------------------------------- #


def all_cells(multi_pod_too: bool = True):
    cells = []
    for arch, cfg in ARCHS.items():
        for shape in cells_for(cfg):
            cells.append((arch, shape.name, False))
            if multi_pod_too:
                cells.append((arch, shape.name, True))
    return cells


def use_host_devices(n: int = 512) -> None:
    """Compile on ``n`` host-platform devices, the production meshes'
    count.  Call before anything initialises a JAX backend; the sweep's
    child processes inherit the setting."""
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")


def main() -> int:
    use_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=1)
    ap.add_argument("--out", default=ARTIFACT_DIR)
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    if args.all:
        cells = all_cells()
        procs: Dict[Any, Tuple] = {}
        failures = []
        todo = list(cells)
        while todo or procs:
            while todo and len(procs) < args.jobs:
                arch, shape, mp = todo.pop(0)
                mesh_tag = "2x16x16" if mp else "16x16"
                path = os.path.join(args.out, f"{arch}__{shape}__{mesh_tag}.json")
                if args.skip_existing and os.path.exists(path):
                    print(f"skip {arch} {shape} {mesh_tag}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--out", args.out]
                if mp:
                    cmd.append("--multi-pod")
                p = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                     stderr=subprocess.STDOUT)
                procs[p] = (arch, shape, mp)
            for p in list(procs):
                if p.poll() is not None:
                    arch, shape, mp = procs.pop(p)
                    out = p.stdout.read().decode()
                    status = "OK" if p.returncode == 0 else "FAIL"
                    print(f"[{status}] {arch} {shape} {'2pod' if mp else '1pod'}")
                    if p.returncode != 0:
                        failures.append((arch, shape, mp, out[-2000:]))
            time.sleep(1.0)
        for arch, shape, mp, out in failures:
            print(f"--- FAILURE {arch} {shape} mp={mp} ---\n{out}\n")
        return 1 if failures else 0

    rec = run_cell(args.arch, args.shape, args.multi_pod, args.out)
    print(json.dumps({k: rec.get(k) for k in
                      ("arch", "shape", "mesh", "compile_s",
                       "cost_compile_s", "cost_pass_error")}, indent=1))
    print(json.dumps(rec["memory"], indent=1))
    if "roofline" in rec:
        print(json.dumps(rec["roofline"], indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
