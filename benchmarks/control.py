"""Readings for the limits of ``correct``: the program's numbers on many
seeds (the lower reading), the CONTROL's (the reference computed with
int8 operands, put in the program's place) and, for a training cell, the
planted faults' (the upper readings), with the first step's loss gap,
which is read and not compared. Not part of a benchmark run: the
builder of a ``benchmark`` PR runs it on the chip at the cell's own size,
several seeds in one process, and writes the limits into the cell's file
from what it prints.

    python3 benchmarks/control.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 1,2,3 --seconds 25

One JSON line per seed: ``{"seed", "program": {check: value},
"control": {...}, "half_batch": {...}, "state_unchanged": {...}}``.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def train_readings(ctx, driver, control: bool):
    """The control and the two planted faults, each put in the program's
    place against the reference the run already computed."""
    cell, spec = ctx.cell, ctx.cell.file["train"]
    out = {}
    if not control:
        return out
    for name, kw in (("control", {"quant": "int8"}),
                     ("half_batch", {"fault": "half_batch"}),
                     ("state_unchanged", {"fault": "state_unchanged"})):
        alt = cell.reference.train_reference(cell.config, ctx.seed,
                                             ctx.facts["followed"],
                                             spec["optimizer"], **kw)
        loss1 = abs(alt["losses"][0] - ctx.facts["reference"]["losses"][0]) \
            / ctx.facts["reference"]["losses"][0]
        out[name] = {c["name"]: (c["value"], c.get("leaf", ""))
                     for c in driver.compare(
                         cell.layout, driver.as_program(cell.layout, alt),
                         ctx.facts["reference"], cell.file["limits"])}
        out[name]["loss1_gap"] = (loss1, "")
    return out


def serve_readings(ctx, driver, control: bool):
    if not control:
        return {}
    ctl = ctx.cell.reference.serve_reference(
        ctx.cell.config, ctx.seed, ctx.facts["sample"], quant="int8")
    return {"control": {"served_logit_gap": driver.control_gap(
        ctx.facts["reference"], ctl)}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}

    from benchmarks import harness
    cell = harness.Cell(args.workload)
    import jax
    from paddle_tpu.obs import xla_cache
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        harness.log(f"control readings need {cell.chips} TPU chip(s); jax "
                    f"found {len(devices)} x {devices[0].platform}")
        return 1
    peaks = harness.load_peaks(devices[0].device_kind)
    xla_cache.setup()
    readings = train_readings if cell.driver == "train" else serve_readings
    driver = harness.load_driver(cell)
    for seed in seeds:
        ctx = harness.make_context(cell, seed, args.seconds, False,
                                   time.perf_counter(),
                                   devices[:cell.chips], peaks)
        out = driver.run(ctx)
        line = {"seed": seed, "correct": out["correct"],
                "failed": out["failed"], "attempted": out["attempted"],
                "metrics": out["metrics"],
                "program": {c["name"]: c["value"] for c in out["checks"]}}
        line["leaves"] = {c["name"]: c["leaf"] for c in out["checks"]
                          if "leaf" in c}
        line.update(readings(ctx, driver, seed in control))
        print(json.dumps(line), flush=True)
        del ctx, out
        harness.free_device_memory()
    return 0


if __name__ == "__main__":
    sys.exit(main())
