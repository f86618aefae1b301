"""``python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>``: run one cell of ``BENCHMARK.json`` on the chip(s) this
machine holds and print the contract's JSON object as the last line of
standard output. One process holds the chip from start to finish; nothing
is fetched; weights and traffic come from ``--seed``.

``__main__`` accepts nothing but a TPU whose ``device_kind`` is in
``benchmarks/peaks.json`` and at least as many chips as the cell asks
for; it exits non-zero and prints no result otherwise. The cell runner
(``harness.run_cell``) is a function of the cell's data, which is how
``tests/benchmark/test_benchmark.py`` runs toy cells on the CPU.
"""

import time

T_START = time.perf_counter()        # set-up is counted from here

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks import harness
    cell = harness.Cell(args.workload)
    import jax
    from paddle_tpu.obs import xla_cache
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        harness.log(f"the benchmark needs a TPU; jax found {dev.platform!r}")
        return 1
    if len(devices) < cell.chips:
        harness.log(f"{cell.name} needs {cell.chips} chip(s); jax found "
                    f"{len(devices)}")
        return 1
    peaks = harness.load_peaks(dev.device_kind)      # unknown kind: raises
    cache_dir = xla_cache.setup()                    # before any compile
    harness.log(f"jax {jax.__version__} on {len(devices)} x "
                f"{dev.device_kind}; compile cache {cache_dir} "
                f"({xla_cache.cache_entry_count()} entries); cell "
                f"{cell.name} seed {args.seed} seconds {args.seconds} "
                f"trace {args.trace}")
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START,
                              devices[:cell.chips], peaks)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
