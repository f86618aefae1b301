"""Top-level picklable mappers for test_xmap: spawn workers unpickle these
by importing THIS module, which deliberately avoids jax so worker startup
stays cheap."""

import time

import numpy as np


def square(x):
    return x * x


def slow_square(x):
    # jitter completion order so ordered/unordered behavior is observable
    time.sleep(0.05 if (x % 3) == 0 else 0.0)
    return x * x


def boom_on_3(x):
    if x == 3:
        raise ValueError("sample 3 is poison")
    return x


def burn(x):
    """CPU-bound mapper (~100 ms/call): heavy enough that every worker
    of a pool is up before the calls run out."""
    a = np.random.RandomState(x).rand(600, 600)
    for _ in range(20):
        a = a @ a.T
        a /= np.abs(a).max()
    return float(a[0, 0])


def burn_with_pid(x):
    """``burn`` and the process that ran it."""
    import os
    return burn(x), os.getpid()


def die_hard(x):
    """Simulate a segfault/OOM-kill: the worker dies without posting any
    sentinel (os._exit skips all cleanup)."""
    import os
    if x == 2:
        os._exit(11)
    return x
