"""What of ``served_logit_gap`` is precision and what is expert routing
(PERF.md section 6, PR 29): the float32 reference of
``serve-pangu718b-closed64`` against ITSELF with operands rounded to
bfloat16 and to int8, on seeded random sequences at the published widths.
For each precision: the share of token-layers whose chosen experts differ,
the share that touches an expert held here, and the gap and logit-error
statistics with and without such a swap. On the chip, from the checkout's
root: ``python3 experiments/routing_vs_precision.py <seed> <sequences>
<tokens each>`` (6 x 2,048: 28 + 17 + 17 s)."""
import json, os, sys, time
sys.path.insert(0, os.getcwd())
import numpy as np, jax
from benchmarks import harness
cell = harness.Cell("serve-pangu718b-closed64")
ref = cell.reference
z = ref.dims(cell.config)
seed = int(sys.argv[1]); n_seq = int(sys.argv[2]); T = int(sys.argv[3])
rng = np.random.RandomState(seed % 2**32)
seqs = [(rng.randint(1, z.V, T).astype(np.int32), np.arange(T, dtype=np.int32)) for _ in range(n_seq)]
out = {}
for quant in (None, "bfloat16", "int8"):
    t = time.time(); routing = []
    logits = ref.forward(cell.config, seed, seqs, quant, routing)
    out[quant] = (logits, routing); print(quant, "s", round(time.time() - t, 1), flush=True)
base, base_r = out[None]
best = [l.max(-1) for l in base]
for quant in ("bfloat16", "int8"):
    logits, routing = out[quant]
    gaps = np.concatenate([b - l0[np.arange(len(l0)), l.argmax(-1)] for b, l0, l in zip(best, base, logits)])
    err = np.concatenate([np.abs(l - l0).mean(-1) for l0, l in zip(base, logits)])
    diff = held = total = 0
    per_token = np.zeros(sum(len(b) for b in best), bool)
    for layer_r, layer_b in zip(routing, base_r):
        off = 0
        for a, b in zip(layer_r, layer_b):
            sa, sb = np.sort(a, -1), np.sort(b, -1)
            d = (sa != sb).any(-1)
            sym = [set(x) ^ set(y) for x, y in zip(a[d], b[d])]
            h = np.array([any(e < z.held_first + z.held and e >= z.held_first for e in s) for s in sym], bool)
            diff += d.sum(); held += h.sum(); total += len(d)
            idx = np.flatnonzero(d)[h] + off
            per_token[idx] = True; off += len(d)
    q = lambda p: float(np.percentile(gaps, p))
    print(json.dumps({"quant": quant, "tokens": int(len(gaps)), "token_layers": int(total),
        "routing_differs_share": diff / total, "touches_held_share": held / total,
        "tokens_with_held_flip_share": float(per_token.mean()),
        "argmax_differs_share": float((gaps > 0).mean()), "gap_max": float(gaps.max()), "gap_p999": q(99.9), "gap_p99": q(99), "gap_p95": q(95), "gap_mean": float(gaps.mean()),
        "gap_max_no_held_flip": float(gaps[~per_token].max()), "gap_p99_no_held_flip": float(np.percentile(gaps[~per_token], 99)),
        "mean_abs_logit_err_p50": float(np.median(err)), "mean_abs_logit_err_no_flip_p50": float(np.median(err[~per_token])), "mean_abs_logit_err_flip_p50": float(np.median(err[per_token])) if per_token.any() else None}), flush=True)
