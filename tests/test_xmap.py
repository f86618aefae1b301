"""Multi-process mapper (VERDICT r4 #8) — the xmap_readers analog
(reference: ``v2/reader/decorator.py:233-292``; image loader
``utils/image_multiproc.py``). Correctness and where the work ran are
asserted; how fast it ran is the host's, and is not."""

import multiprocessing as mp
import os
import time

import numpy as np
import pytest

import xmap_helpers as H
from paddle_tpu import data
from paddle_tpu.data import image as im


def _ints(n):
    def reader():
        return iter(range(n))
    return reader


def test_xmap_ordered_matches_serial():
    got = list(data.xmap(H.slow_square, _ints(12), processes=2)())
    assert got == [x * x for x in range(12)]


def test_xmap_unordered_same_multiset():
    got = list(data.xmap(H.slow_square, _ints(12), processes=2,
                         ordered=False)())
    assert sorted(got) == [x * x for x in range(12)]


def test_xmap_worker_error_propagates():
    with pytest.raises(RuntimeError, match="sample 3 is poison"):
        list(data.xmap(H.boom_on_3, _ints(8), processes=2)())


def test_xmap_dead_worker_raises_instead_of_hanging():
    """A worker killed without cleanup (segfault/OOM-kill analog) must be
    detected as a corpse, not waited on forever."""
    with pytest.raises(RuntimeError, match="died with exitcode"):
        list(data.xmap(H.die_hard, _ints(8), processes=1)())


def test_xmap_source_reader_error_propagates_no_hang():
    """A source reader that raises mid-iteration must surface the error
    after the mapped results — never strand the consumer on a queue."""
    def flaky():
        def it():
            yield from range(5)
            raise IOError("disk went away")
        return it()
    with pytest.raises(IOError, match="disk went away"):
        list(data.xmap(H.square, flaky, processes=2)())


def test_xmap_early_abandon_shuts_down_workers():
    it = data.xmap(H.square, _ints(1000), processes=2, buffer=4)()
    got = [next(it) for _ in range(3)]
    assert got == [0, 1, 4]
    it.close()
    deadline = time.time() + 10
    while time.time() < deadline and mp.active_children():
        time.sleep(0.1)
    assert not mp.active_children()


def test_xmap_train_augment_pickles_and_is_worker_independent():
    """TrainAugment crosses the process boundary and its per-sample rng
    (seeded from the image bytes) gives results independent of worker
    count or assignment."""
    rng = np.random.RandomState(0)
    imgs = [rng.rand(10, 8, 3).astype(np.float32) for _ in range(6)]

    def rdr():
        return iter(imgs)

    tf = im.TrainAugment((4, 4), (6, 6), mean=[0, 0, 0], seed=7)
    serial = [tf(x) for x in imgs]
    par1 = list(data.xmap(tf, rdr, processes=1)())
    par2 = list(data.xmap(tf, rdr, processes=2)())
    for s, a, b in zip(serial, par1, par2):
        np.testing.assert_array_equal(s, a)
        np.testing.assert_array_equal(s, b)
    # cross-epoch diversity: set_epoch reseeds the per-sample draws
    epoch1 = [tf.set_epoch(1)(x) for x in imgs]
    assert any(not np.array_equal(s, e) for s, e in zip(serial, epoch1))


def test_xmap_runs_cpu_bound_mapper_in_worker_processes():
    """What ``processes=`` is for: the CPU-bound work leaves the parent
    (and its GIL) and spreads over the workers. How much faster that is
    depends on the host's free cores, so it is not asserted."""
    n = 24
    serial = [H.burn(x) for x in range(n)]
    out = list(data.xmap(H.burn_with_pid, _ints(n), processes=4,
                         buffer=16)())
    assert [v for v, _ in out] == serial
    pids = {pid for _, pid in out}
    assert len(pids) > 1 and os.getpid() not in pids
