import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""§Perf hillclimb driver: run dry-run variants for the three chosen cells
and log hypothesis -> before -> after into results/hillclimb.json.

Cells (selection rationale in EXPERIMENTS.md §Perf):
  1. granite-moe-3b-a800m x train_4k   — most collective-bound cell AND the
     paper's technique (dynamic-rate experts).
  2. qwen2-72b x train_4k              — largest model, worst absolute bound.
  3. qwen2-72b x decode_32k            — memory-bound serving regime, worst
     useful-flops fraction.

Usage: PYTHONPATH=src python -m benchmarks.hillclimb [--only CELL_IDX]
"""
import argparse
import json

from repro.launch.dryrun import run_cell

PLAN = [
    # (arch, shape, variant, hypothesis)
    ("granite-moe-3b-a800m", "train_4k", "base", "baseline"),
    ("granite-moe-3b-a800m", "train_4k", "moe_local16",
     "the N-global rank-cumsum + cross-shard scatter dominate the "
     "collective term; per-data-shard dispatch keeps tokens local until "
     "the expert einsum -> expect >=2x lower collective bytes"),
    ("granite-moe-3b-a800m", "train_4k", "moe_local16+cf1",
     "capacity factor 1.25->1.0 cuts expert slab bytes ~20% on top"),
    ("granite-moe-3b-a800m", "train_4k", "moe_local16+mb4",
     "4 microbatches quarter the live dispatch buffers (memory term) at "
     "the cost of 4x smaller per-step einsums"),

    ("qwen2-72b", "train_4k", "base", "baseline"),
    ("qwen2-72b", "train_4k", "mb4",
     "activation memory (temp bytes) dominates the memory term; 4 "
     "microbatches cut live activations ~4x with <5% extra flops"),
    ("qwen2-72b", "train_4k", "f32grads",
     "negative control: f32 gradient all-reduce should ~double the "
     "cross-replica collective bytes vs the bf16-compressed baseline"),

    ("qwen2-72b", "decode_32k", "base", "baseline"),
    ("qwen2-72b", "decode_32k", "kv_int8",
     "decode is KV-bandwidth-bound; int8 cache halves bytes-per-token"),
    ("qwen2-72b", "decode_32k", "kv_int8+seqshard",
     "GQA KV replication leaves the model axis idle for the cache; "
     "seq-sharding the ring over `model` cuts per-chip cache memory 16x "
     "for one tiny per-token softmax all-reduce"),
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="results/hillclimb.json")
    ap.add_argument("--only", default=None,
                    help="comma list of indices into PLAN")
    args = ap.parse_args()

    results = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["variant"]) for r in results
            if r.get("status") == "ok"}

    idxs = (range(len(PLAN)) if args.only is None
            else [int(i) for i in args.only.split(",")])
    for i in idxs:
        arch, shape, variant, hyp = PLAN[i]
        if (arch, shape, variant) in done:
            print(f"[hillclimb] skip (done): {arch}/{shape}/{variant}")
            continue
        print(f"[hillclimb] {arch}/{shape}/{variant} ...", flush=True)
        rec = run_cell(arch, shape, multi_pod=False, probes=True,
                       variant=variant)
        rec["hypothesis"] = hyp
        results = [r for r in results
                   if (r["arch"], r["shape"], r["variant"]) != (arch, shape, variant)]
        results.append(rec)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        if rec["status"] == "ok":
            t = rec["roofline"]
            print(f"[hillclimb] -> compute {t['compute_s']:.3g}s  memory "
                  f"{t['memory_s']:.3g}s  collective {t['collective_s']:.3g}s "
                  f" bottleneck={rec['bottleneck']}", flush=True)
        else:
            print(f"[hillclimb] -> {rec['status']}: {rec.get('error', '')[:200]}",
                  flush=True)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
