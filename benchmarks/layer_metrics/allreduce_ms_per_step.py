"""Trainer loop: milliseconds of gradient all-reduce on the device a
training step, from the profiler's trace: the device seconds of every
operation kind whose name begins with ``all-reduce`` (``op_seconds``,
averaged over the chips) over the steps that ended in the traced window.

The kinds a chip's trace names (TPU v5 lite, four chips, the step's
all-reduces compiled synchronous and asynchronous alike): ``all-reduce``
alone. An asynchronous all-reduce keeps its instruction's name on the
device's ``XLA Ops`` line, where it runs on the TensorCore; the
``all-reduce-start`` / ``-done`` forms are counted should a compile name
them. A step with no collective reads 0."""

PREFIX = "all-reduce"


def read(ctx):
    steps = ctx.facts.get("traced_steps")
    if not steps or ctx.trace is None:
        return None
    seconds = sum(s for kind, s in ctx.trace["op_seconds"].items()
                  if kind.startswith(PREFIX))
    return 1e3 * seconds / steps
