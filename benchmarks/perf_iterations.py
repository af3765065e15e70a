"""§Perf hillclimbing harness: hypothesis -> change -> re-lower -> measure.

Each iteration re-runs a dry-run cell with a config variant
(`parallel_overrides`) and reports the roofline-term deltas vs the
stored baseline artifact.  Results land in experiments/perf/ with the
variant tag; the narrative log lives in EXPERIMENTS.md §Perf.

Run a single iteration:
  PYTHONPATH=src python -m benchmarks.perf_iterations \
      --arch granite-3-8b --shape decode_32k --variant kv_fp8

Variants are declared in VARIANTS below — each is (overrides, hypothesis).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ART = os.path.join(os.path.dirname(__file__), "..", "experiments", "dryrun")
PERF = os.path.join(os.path.dirname(__file__), "..", "experiments", "perf")

VARIANTS = {
    # decode: KV cache in fp8 -> cache traffic (the dominant memory term
    # of decode) halves; collective term unchanged.
    "kv_fp8": (dict(kv_cache_dtype="float8_e4m3fn"),
               "halve decode memory term via fp8 KV cache"),
    # train: remat 'dots' keeps matmul outputs -> removes the fwd
    # recompute from the backward (flops -1/3) at higher live memory.
    "remat_dots": (dict(remat="dots"),
                   "cut compute term ~25-33% by saving matmul outputs"),
    "remat_none": (dict(remat="none"),
                   "no remat: lowest flops, highest memory (bound check)"),
    # attention tile size: diagonal-tile waste ~ c/(2s) of attention flops
    "attn_chunk_2048": (dict(attention_chunk=2048),
                        "smaller causal tiles -> less masked-tile waste"),
    # logits head in bf16 halves head bytes (quality note in EXPERIMENTS)
    "logits_bf16": (dict(logits_fp32=False),
                    "halve LM-head bytes (memory term) via bf16 logits"),
    # MoE: tighter capacity cuts expert GEMM volume proportionally
    "moe_cap_1_0": (dict(moe_capacity_factor=1.0),
                    "cut expert GEMM volume 20% via capacity factor 1.0"),
    # MoE EP combine in bf16: halves the dominant per-layer psum bytes
    "moe_psum_bf16": (dict(moe_psum_dtype="bfloat16"),
                      "halve MoE combine collective bytes via bf16 psum"),
    # combined best-of variants
    "combo_decode": (dict(kv_cache_dtype="float8_e4m3fn", logits_fp32=False),
                     "fp8 KV + bf16 logits: compound memory-term win"),
    "combo_moe_train": (dict(moe_psum_dtype="bfloat16", moe_capacity_factor=1.0,
                             remat="dots"),
                        "bf16 psum + capacity 1.0 + dots remat"),
    # no FSDP (pure TP + replicated params): kills per-layer all-gathers,
    # pays replicated-param memory (collective-term experiment)
    "no_fsdp": (dict(fsdp=False),
                "remove FSDP weight all-gathers -> collective term drops"),
    "mb1": (dict(microbatches=1), "single microbatch (memory experiment)"),
    # serving layout: params TP-only (replicated over data) — decode must
    # not re-all-gather FSDP weight shards every token
    "combo_serve": (dict(fsdp=False, kv_cache_dtype="float8_e4m3fn",
                         logits_fp32=False),
                    "TP-only serving layout + fp8 KV + bf16 logits"),
    # row-parallel attention: TP-shard the d_model dim when head counts
    # don't divide the axis (kills weight replication without FSDP)
    "rp_attn_serve": (dict(fsdp=False, row_parallel_attn=True),
                      "TP-only + row-parallel attn: no replication, no gathers"),
    "rp_combo_serve": (dict(fsdp=False, row_parallel_attn=True,
                            kv_cache_dtype="float8_e4m3fn", logits_fp32=False),
                       "row-parallel TP serving + fp8 KV + bf16 logits"),
}


def load_baseline(arch, shape, mesh="16x16"):
    p = os.path.join(ART, f"{arch}__{shape}__{mesh}.json")
    return json.load(open(p))


def compare(base, new):
    rows = []
    b, n = base["roofline"], new["roofline"]
    for k in ("t_compute_s", "t_memory_s", "t_collective_s"):
        delta = (n[k] - b[k]) / b[k] if b[k] else 0.0
        rows.append((k, b[k], n[k], delta))
    rows.append(("roofline_fraction", b["roofline_fraction"],
                 n["roofline_fraction"],
                 (n["roofline_fraction"] - b["roofline_fraction"])
                 / max(b["roofline_fraction"], 1e-12)))
    rows.append(("bottleneck", b["bottleneck"], n["bottleneck"], ""))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", required=True, choices=sorted(VARIANTS))
    args = ap.parse_args()

    from repro.launch.dryrun import run_cell, use_host_devices
    use_host_devices()
    overrides, hypothesis = VARIANTS[args.variant]
    base = load_baseline(args.arch, args.shape)
    rec = run_cell(args.arch, args.shape, multi_pod=False, out_dir=PERF,
                   parallel_overrides=overrides, tag=f"__{args.variant}")
    print(f"# hypothesis: {hypothesis}")
    print("metric,baseline,variant,delta")
    for k, b, n, d in compare(base, rec):
        if isinstance(d, float):
            print(f"{k},{b:.6g},{n:.6g},{d:+.1%}")
        else:
            print(f"{k},{b},{n},")
    print(f"peak_bytes,{base['memory']['peak_bytes_per_device']},"
          f"{rec['memory']['peak_bytes_per_device']},")


if __name__ == "__main__":
    main()
