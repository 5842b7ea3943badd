"""Per-chip throughput sweep for the engine fast path.

Sweeps (batch, remat, loss_chunk, micro_batches) on the h2048 primary
config through bench.py's own `--single` subprocess entry point — same
timing methodology as the headline benchmark (one implementation), with
OOM isolation per candidate.

Run:  python tools/perf_sweep.py
      python tools/perf_sweep.py --blocks   # flash block-size timing grid

`--blocks` sweeps the flash-attention (block_q, block_k) grid end-to-end
through the train step via the PADDLE_TUNE_BLOCKS env override (the same
knob kernels/tuning.py resolves last, so each child process runs the
whole step pinned to one candidate). The printed grid is where the
checked-in fallback table in kernels/tuning.py comes from; on a chip it
also validates what the on-device autotuner picked.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")

H2048 = dict(vocab_size=32000, hidden_size=2048, intermediate_size=5504,
             num_hidden_layers=16, num_attention_heads=16,
             max_position_embeddings=2048)

# r4 measured on TPU v5e-16G (2026-07): full remat b8 ~17.0k tok/s;
# remat='half' OOMed at every batch (the then-f32 AdamW moments, 7.5GB,
# left no room); 'dots' + chunked CE + 2 accumulated micro-batches won at
# ~17.5k. r5: moments='bf16' (stochastic-rounded) frees 3.8GB and
# 'factored' ~7.3GB — sweep 'half' and no-remat at the freed budget.
#
# r5 RESULT (2026-08-01, v5e, before PR 1; not re-measured): the decisive
# lever was none of the above — xprof showed ~17% of the step in the layer
# scan's dynamic-update-slice residual stacking. With the layer loop
# UNROLLED (engine `unroll`, default on a 1x1x1 mesh) no-remat fits at M=2
# even with f32 moments: b8 21.4k tok/s / 0.64 MFU, b32 23.1k / 0.69 MFU
# (sweep history: dots+M2 17.7k -> unroll 19.1k -> lean 19.3k ->
# no-remat 21.0k). tools/perf_sweep2.py holds the follow-up grid.
SPECS = [
    # r4 champion re-run (comparison point)
    {"cfg": H2048, "batch": 8, "seq": 1024, "remat": "dots",
     "loss_chunk": 128, "micro_batches": 2},
    # lean moments + half remat: the predicted r5 winner
    {"cfg": H2048, "batch": 8, "seq": 1024, "remat": "half",
     "loss_chunk": 128, "moments": "bf16"},
    {"cfg": H2048, "batch": 8, "seq": 1024, "remat": "half",
     "loss_chunk": 128, "moments": "factored"},
    # lean moments + dots (r4 champion's remat, smaller opt state)
    {"cfg": H2048, "batch": 8, "seq": 1024, "remat": "dots",
     "loss_chunk": 128, "micro_batches": 2, "moments": "bf16"},
    {"cfg": H2048, "batch": 8, "seq": 1024, "remat": "dots",
     "loss_chunk": 128, "moments": "bf16"},
    # no remat at all — fits only if activations squeeze into ~10GB
    {"cfg": H2048, "batch": 8, "seq": 1024, "remat": False,
     "loss_chunk": 128, "moments": "factored"},
    {"cfg": H2048, "batch": 4, "seq": 1024, "remat": False,
     "loss_chunk": 128, "moments": "bf16"},
    # bigger batch under lean moments
    {"cfg": H2048, "batch": 16, "seq": 1024, "remat": "half",
     "loss_chunk": 128, "moments": "bf16"},
]


# flash (block_q, block_k) grid for --blocks: the v5e-plausible tile sizes
# (multiples of the 8x128 register tile that fit VMEM at head_dim 128)
BLOCK_GRID = [(256, 512), (512, 512), (512, 1024), (1024, 512),
              (1024, 1024)]


def main_blocks():
    """Time the h2048 s1024 train step once per flash block candidate."""
    spec = {"cfg": H2048, "batch": 8, "seq": 1024, "remat": False,
            "loss_chunk": 128, "micro_batches": 2}
    results = []
    for bq, bk in BLOCK_GRID:
        env = dict(os.environ)
        env["PADDLE_TUNE_BLOCKS"] = json.dumps({
            "flash_fwd": {"block_q": bq, "block_k": bk},
            "flash_bwd": {"block_q": bq, "block_k": bk}})
        try:
            out = subprocess.run(
                [sys.executable, BENCH, "--single", json.dumps(spec)],
                capture_output=True, text=True, timeout=900, cwd=REPO,
                env=env)
            got = None
            for line in out.stdout.splitlines():
                if line.startswith("BENCH_RESULT "):
                    got = json.loads(line[len("BENCH_RESULT "):])
            if got:
                results.append({"block_q": bq, "block_k": bk,
                                "tps": got["tps"]})
                print(f"block_q={bq} block_k={bk} -> {got['tps']:.1f} tok/s",
                      flush=True)
            else:
                tail = out.stderr[-500:].replace("\n", " ")
                print(f"block_q={bq} block_k={bk} -> FAILED: {tail}",
                      flush=True)
        except subprocess.TimeoutExpired:
            print(f"block_q={bq} block_k={bk} -> TIMEOUT", flush=True)
    if results:
        best = max(results, key=lambda r: r["tps"])
        print("BEST_BLOCKS " + json.dumps(best))


def main():
    results = []
    for spec in SPECS:
        label = {k: v for k, v in spec.items() if k != "cfg"}
        try:
            out = subprocess.run(
                [sys.executable, BENCH, "--single", json.dumps(spec)],
                capture_output=True, text=True, timeout=900, cwd=REPO)
            got = None
            for line in out.stdout.splitlines():
                if line.startswith("BENCH_RESULT "):
                    got = json.loads(line[len("BENCH_RESULT "):])
            if got:
                got["spec"] = spec
                results.append(got)
                print(f"{label} -> {got['tps']:.1f} tok/s", flush=True)
            else:
                tail = out.stderr[-500:].replace("\n", " ")
                print(f"{label} -> FAILED: {tail}", flush=True)
        except subprocess.TimeoutExpired:
            print(f"{label} -> TIMEOUT", flush=True)
    if results:
        best = max(results, key=lambda r: r["tps"])
        print("BEST " + json.dumps(
            {"tps": best["tps"],
             "spec": {k: v for k, v in best["spec"].items() if k != "cfg"}}))


if __name__ == "__main__":
    if sys.argv[1:] == ["--blocks"]:
        main_blocks()
    else:
        main()
