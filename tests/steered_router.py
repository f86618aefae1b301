"""A router steered token by token, for the tests of
``nn/moe.py:HeldExpertsFFN``'s row windows (``test_latent_moe.py``,
``test_longcat.py``): how many (token, expert) pairs a share keeps is set
exactly, against the window's ``ROW_WINDOW`` rows.

The first ``K`` columns of the input are marks of +1 or -1 and the
router's row ``j`` is ``+c_j`` at output ``yes[j]`` and ``-c_j`` at
``no[j]`` (zero elsewhere, ``c_j`` from 8 down): a token's ``j``-th
choice is ``yes[j]`` where its mark is +1 and ``no[j]`` where it is -1,
whatever the scoring, and every other output scores 0."""

import numpy as np

from paddle_tpu.nn.moe import ROW_WINDOW as M

TOKENS = 200

# kept pairs of ``TOKENS * K`` against the window, and the tokens that are
# live (None: all). ``all`` is every pair (``TOKENS * K`` is no multiple
# of the window: the last one hangs over the end of the sorted rows).
CASES = {
    "none_kept": (0, None),
    "fewer_than_a_window": (25, None),
    "exactly_a_window": (M, None),
    "a_window_and_one": (M + 1, None),
    "experts_straddle_two_edges": (2 * M + 64, None),
    "every_pair_kept": ("all", None),
    "live_mask_on_top": (2 * M + 64, 120),
}


def steered(case, x, width, yes, no, seed=0):
    """``(router [D, width], x with its marks, live [TOKENS] or None, the
    live tokens' choices among ``yes``)`` for one of ``CASES``:
    ``kept // K`` tokens take every ``yes``, ``kept % K`` tokens take
    ``yes[0]`` alone, in a shuffled order."""
    K, (kept, n_live) = len(yes), CASES[case]
    kept = TOKENS * K if kept == "all" else kept
    marks = -np.ones((TOKENS, K), np.float32)
    marks[:kept // K] = 1.0
    marks[kept // K:kept // K + kept % K, 0] = 1.0
    marks = marks[np.random.RandomState(seed).permutation(TOKENS)]
    router = np.zeros((x.shape[1], width), np.float32)
    for j in range(K):
        router[j, yes[j]], router[j, no[j]] = 8.0 - j, j - 8.0
    x = np.array(x, np.float32)
    x[:, :K] = marks
    live = None if n_live is None else np.arange(TOKENS) < n_live
    return router, x, live, int((marks[:n_live] > 0).sum())
