"""Driver of a serving cell whose model ROUTES its tokens (a router that
takes the k best of n experts): the serve driver as it is, the same
engine, scheduler, loop, stamps, sample and reference
(``benchmarks/drivers/serve.py:run``), with further checks in ``correct``.

The serve driver's one check is the WIDEST gap by which a served token's
logit lies below the reference's best. Under a router that statistic is
set by which expert comes k-th and which (k+1)-th, at any precision: a
swap on a few tokens in a thousand moves their logits further than int8
operands move all the others, so the widest gap reads alike at the
stated precision and one below it and cannot fail the control. A
percentile of the same gaps over the same sampled tokens leaves those few
tokens out and reads the precision. Every key ``served_logit_gap_p<q>`` of
the cell file's ``limits`` is such a check (``q`` a percentile, 0 to 100)
beside ``served_logit_gap``, which stays and still fails a wrong block.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict, List

import numpy as np

from benchmarks import harness

# the sibling driver, found by name as the harness finds any driver
serve = importlib.import_module("benchmarks.drivers.serve")

WIDEST = "served_logit_gap"
PERCENTILE = WIDEST + "_p"
# what a control run prints beside the widest gap (control.py has no cell
# limits at hand when it asks)
CONTROL_PERCENTILES = (90.0, 95.0, 99.0)


def token_gaps(ref_logits, picks) -> np.ndarray:
    """For every position of every sampled request, how far the picked
    token's logit lies below the reference's best there."""
    return np.concatenate([
        ref.max(axis=-1) - ref[np.arange(len(p)), np.asarray(p)]
        for ref, p in zip(ref_logits, picks)]).astype(np.float64)


def readings(gaps: np.ndarray, names) -> Dict[str, float]:
    """The statistic each name asks for, over the pooled gaps."""
    out = {}
    for name in names:
        if name == WIDEST:
            out[name] = float(gaps.max())
        elif name.startswith(PERCENTILE):
            out[name] = float(np.percentile(
                gaps, float(name[len(PERCENTILE):])))
        else:
            raise KeyError(f"no check {name!r}: {WIDEST} or "
                           f"{PERCENTILE}<percentile>")
    return out


def serve_checks(sample, ref_logits, limits: Dict[str, float]) -> List[Dict]:
    gaps = token_gaps(ref_logits, [tokens for _, tokens in sample])
    return [harness.check(name, value, limits[name])
            for name, value in readings(gaps, limits).items()]


def control_gap(ref_logits, control_logits) -> Dict[str, float]:
    """The control's readings: at each position of the same prompts and
    tokens, the gap in the reference of the token that the lower precision
    puts first; the widest and ``CONTROL_PERCENTILES``."""
    gaps = token_gaps(ref_logits,
                      [ctl.argmax(axis=-1) for ctl in control_logits])
    return readings(gaps, [WIDEST] + [f"{PERCENTILE}{q:g}"
                                      for q in CONTROL_PERCENTILES])


def run(ctx) -> Dict[str, Any]:
    out = serve.run(ctx)        # its ``correct`` holds the widest gap
    tails = {k: round(v, 3) for k, v in out["metrics"].items()
             if k != "serve_tokens_per_s"}
    harness.log(f"serve: tails in the window {tails}")
    if "sample" not in ctx.facts:
        return out
    limits = ctx.cell.file["limits"]
    out["checks"] = serve_checks(ctx.facts["sample"],
                                 ctx.facts["reference"], limits)
    out["correct"] = bool(out["correct"]
                          and all(c["ok"] for c in out["checks"]))
    return out
