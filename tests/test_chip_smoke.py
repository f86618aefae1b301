"""``chip_smoke.py``'s legs at a toy size on the CPU, in interpret mode.

The chip script's legs are functions of its ``Sizes``; this runs the same
control flow (train through the Trainer, serve through the scheduler with
admission and eviction, the three tick variants, the one-shot prefill's
pages against the scatter, every kernel against
its oracle, then dp=4 and tp=4 on four of the forced CPU devices) without
a chip, so that a refactor cannot break the script unseen. What only the
chip can show (Mosaic compiles, the device itself) the legs skip off-TPU,
and ``main`` accepts nothing but a TPU.
"""

import os
import sys

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import chip_smoke  # noqa: E402
from paddle_tpu.core import mesh as mesh_lib  # noqa: E402

TOY = chip_smoke.Sizes(
    vocab=128, dim=32, layers=2, heads=4, ffn=64, max_len=32, batch=4,
    train_steps=4, slots=2, block_size=8, requests=4, variant_requests=2,
    prompt=(4, 20), new_tokens=(3, 5), speculative=2, prefill_chunk=8)


def test_legs_at_toy_size_on_cpu():
    devices = jax.devices()
    trained = chip_smoke.train_leg(
        TOY, mesh=mesh_lib.single_device_mesh(devices[0]))
    chip_smoke.serve_legs(TOY, trained["model"], trained["variables"],
                          attention="paged")
    chip_smoke.prefill_leg(TOY, trained["model"], trained["variables"],
                           attention="paged")
    chip_smoke.kernel_leg(TOY)
    chip_smoke.four_device_leg(TOY, trained["losses"], devices[:4],
                               attention="paged")


def test_main_refuses_anything_but_a_tpu(capsys):
    assert chip_smoke.main() != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out                    # no result line
