"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, in ONE process that holds the chip from start
to finish: ``Trainer.init`` / ``Trainer.train`` on a reader, then the
trained parameters as they are into ``DecodeEngine`` under
``ContinuousBatchingScheduler``, at the full width of the widest model
the repo has measured (``transformer_big``: d1024, dh=128, 8 layers,
seq 2048), then the one-shot prefill's pages against the scatter's and
every Pallas kernel against its float32 oracle. When four
devices are visible the same path runs on four (dp=4 training, tp=4
serving) in the same process. Seeded synthetic data only; no network, no
child that needs a device.

    python3 chip_smoke.py

prints ``jax.__version__`` and the device, runs the legs (each prints
its wall time split into compile and run), and ends with one JSON line
``{"ok": true, "device": {...}}``. Anything that does not hold raises:
there is no ``try/except`` around a leg and no CPU re-run, and
``__main__`` accepts nothing but a TPU whose ``device_kind`` has an entry
in the peaks table. The legs are functions of :class:`Sizes`, so that
``tests/test_chip_smoke.py`` can run the same control flow at a toy size
on the CPU in interpret mode.
"""

from __future__ import annotations

import dataclasses
import json
import re
import sys
import time
from typing import Any, Dict, List, Tuple


@dataclasses.dataclass(frozen=True)
class Sizes:
    """One model and the traffic the legs put through it."""
    vocab: int = 32000
    dim: int = 1024
    layers: int = 8
    heads: int = 8
    ffn: int = 4096
    max_len: int = 2048
    batch: int = 16                      # train: batch x max_len tokens
    train_steps: int = 4
    slots: int = 8
    block_size: int = 16
    requests: int = 16                   # main serve leg; > slots
    variant_requests: int = 4            # each tick variant
    prompt: Tuple[int, int] = (64, 1024)
    new_tokens: Tuple[int, int] = (32, 64)
    speculative: int = 4
    prefill_chunk: int = 256

    @property
    def head_dim(self) -> int:
        return self.dim // self.heads


FULL = Sizes()

# kernel-vs-oracle tolerances, on max |kernel - oracle| / max |oracle|.
# float32 operands: the MXU may still take bf16 passes (2^-8 relative per
# product), so 1e-2, far below the O(1) error of a wrong mask or page.
# bfloat16 / int8 operands: the oracle is given the same rounded values,
# so what is left is the bf16 probability tile and bf16 output rounding.
TOLERANCE = {"float32": 1e-2, "bfloat16": 2e-2, "int8": 2e-2}


def log(msg: str) -> None:
    print(msg, flush=True)


def relative_error(got, want) -> float:
    """The largest difference over the oracle's largest value."""
    import numpy as np
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-6))


def mosaic_kernels(hlo_text: str) -> List[str]:
    """Names of the Pallas kernels that are Mosaic custom calls in a
    compiled program's text (every ``pallas_call`` in
    ``nn/pallas_attention.py`` carries a stable ``name``)."""
    names = []
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.search(r'op_name="[^"]*?/(\w+)/pallas_call', line)
            if m:
                names.append(m.group(1))
    return names


def require_mosaic(hlo_text: str, wanted: Tuple[str, ...], what: str) -> None:
    """On a TPU the named kernels must be Mosaic custom calls in the
    compiled text, so an interpreted or reference path cannot pass. Off
    the TPU (the toy CPU test) the kernels are interpreted by design and
    there is nothing to look for."""
    from paddle_tpu.nn import pallas_mode
    if pallas_mode.interpret():
        return
    found = mosaic_kernels(hlo_text)
    missing = [k for k in wanted if k not in found]
    assert not missing, (f"{what}: kernels {missing} are not Mosaic custom "
                         f"calls in the compiled text (found {found})")
    log(f"  {what}: Mosaic custom calls {sorted(set(found))}")


def make_model(sizes: Sizes):
    from paddle_tpu.models import TransformerLM
    return TransformerLM(vocab=sizes.vocab, dim=sizes.dim,
                         num_layers=sizes.layers, num_heads=sizes.heads,
                         ffn_hidden=sizes.ffn, max_len=sizes.max_len,
                         use_flash=True)


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_leg(sizes: Sizes, mesh=None, seed: int = 0) -> Dict[str, Any]:
    """``Trainer.init`` + ``Trainer.train`` on a reader, flash attention,
    bf16 compute, Adam. The stream is next-token prediction over a
    small corner of the vocabulary (512 tokens, a quarter of a toy
    vocabulary): every step sees a fresh seeded batch, and the loss still
    falls within a few steps because the unigram support is learnable at
    once. Returns the model, the trained variables, the per-step losses
    and the timing."""
    import numpy as np
    import jax
    from paddle_tpu import optim
    from paddle_tpu.core.dtypes import bfloat16_compute, use_policy
    from paddle_tpu.nn import costs
    from paddle_tpu.train import Trainer, events

    model = make_model(sizes)
    support = min(sizes.vocab // 4, 512)

    def reader():
        rng = np.random.RandomState(seed)
        for _ in range(sizes.train_steps):
            toks = rng.randint(0, support,
                               (sizes.batch, sizes.max_len + 1))
            yield {"x": toks[:, :-1].astype(np.int32),
                   "y": toks[:, 1:].astype(np.int32)}

    trainer = Trainer(
        model,
        loss_fn=lambda out, b: costs.softmax_cross_entropy(
            out.reshape(-1, sizes.vocab), b["y"].reshape(-1)),
        optimizer=optim.adam(1e-3), mesh=mesh)
    losses: List[float] = []
    stamps: List[float] = []

    def on_event(e):
        if isinstance(e, events.EndIteration):
            losses.append(e.cost)          # already fetched: a fenced time
            stamps.append(time.perf_counter())

    with use_policy(bfloat16_compute):
        t0 = time.perf_counter()
        trainer.init(jax.random.PRNGKey(seed), next(iter(reader())))
        t_init = time.perf_counter()
        trainer.train(reader, num_passes=1, event_handler=on_event,
                      log_period=0)
        # the step's compiled text, from the persistent cache the first
        # step filled: flash forward and both backward kernels compiled
        lowered, _ = trainer.lower_step(next(iter(reader())))
        t_text = time.perf_counter()
        text = lowered.compile().as_text()
        text_s = time.perf_counter() - t_text
    assert len(losses) == sizes.train_steps >= 4, losses
    assert all(np.isfinite(losses)), f"non-finite loss: {losses}"
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    require_mosaic(text, ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"),
                   "train step")
    steady = np.diff(stamps)
    ts = trainer.train_state
    n_dev = len(jax.tree_util.tree_leaves(ts.params)[0].sharding.device_set)
    log(f"  train: {sizes.train_steps} steps on {n_dev} device(s), losses "
        f"{[round(float(l), 4) for l in losses]}")
    log(f"  train: init {t_init - t0:.1f}s, first step (compile + run) "
        f"{stamps[0] - t_init:.1f}s, later steps "
        f"{[round(float(s), 3) for s in steady]}s, compiled text "
        f"{text_s:.1f}s")
    return {"model": model,
            "variables": {"params": ts.params, "state": ts.state},
            "losses": [float(l) for l in losses]}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def serve_leg(sizes: Sizes, model, variables, name: str, n_requests: int,
              kernel: str, seed: int = 0, **engine_kwargs):
    """The README's serving entry: ``DecodeEngine`` +
    ``ContinuousBatchingScheduler`` over a seeded ``loadgen`` workload
    with ragged prompts and budgets. Every request must finish with the
    token count it asked for, on the paged kernels, with one compile per
    program; ``kernel`` names the Pallas kernel the tick must hold.
    Returns the drained engine."""
    from paddle_tpu.core.dtypes import bfloat16_compute, use_policy
    from paddle_tpu.serve import ContinuousBatchingScheduler, DecodeEngine
    from paddle_tpu.serve.loadgen import make_workload

    with use_policy(bfloat16_compute):
        t0 = time.perf_counter()
        engine = DecodeEngine(model, variables, max_slots=sizes.slots,
                              block_size=sizes.block_size, **engine_kwargs)
        sched = ContinuousBatchingScheduler(engine)
        work = make_workload(n_requests, sizes.vocab, seed=seed,
                             prompt_len=sizes.prompt,
                             max_new=sizes.new_tokens,
                             max_total=engine.context_width)
        reqs = [sched.submit(g.prompt, g.max_new_tokens) for g in work]
        # first step = first admissions + first tick: both programs compile
        t1 = time.perf_counter()
        sched.step()
        t2 = time.perf_counter()
        sched.run()
        t3 = time.perf_counter()
        text = engine.lower_tick().compile().as_text()
    for r in reqs:
        assert r.finish_reason == "length" \
            and len(r.tokens) == r.max_new_tokens, \
            f"{name}: request {r.rid} ended {r.finish_reason!r} with " \
            f"{len(r.tokens)}/{r.max_new_tokens} tokens"
        assert all(0 <= t < sizes.vocab for t in r.tokens), \
            f"{name}: request {r.rid} has a token outside the vocabulary"
    assert engine.attention == "paged", engine.attention
    assert engine.compile_counts() == {"prefill": 1, "tick": 1}, \
        engine.compile_counts()
    assert not engine.active.any() and \
        engine.cache.free_blocks == engine.cache.num_blocks - 1, \
        f"{name}: blocks leaked after every request finished"
    require_mosaic(text, (kernel,), f"serve[{name}] tick")
    tokens = sum(len(r.tokens) for r in reqs)
    log(f"  serve[{name}]: {len(reqs)} requests (prompts "
        f"{min(len(g.prompt) for g in work)}-"
        f"{max(len(g.prompt) for g in work)} tokens), {tokens} new tokens "
        f"in {engine.ticks} ticks, {engine.prefill_chunks} prefill calls, "
        f"kv {engine.cache.quant_dtype}, tp {engine.tp_degree}")
    log(f"  serve[{name}]: build {t1 - t0:.1f}s, first step (compile + "
        f"run) {t2 - t1:.1f}s, run {t3 - t2:.1f}s")
    return engine


def serve_legs(sizes: Sizes, model, variables, **engine_kwargs) -> None:
    """The plain tick under admission and eviction (more requests than
    slots), then the three other tick variants that run different kernel
    code, each on the same weights."""
    assert sizes.requests > sizes.slots
    serve_leg(sizes, model, variables, "plain", sizes.requests,
              "paged_decode", **engine_kwargs)
    n = sizes.variant_requests
    serve_leg(sizes, model, variables, "int8", n, "paged_decode",
              kv_dtype="int8", **engine_kwargs)
    serve_leg(sizes, model, variables, f"speculative={sizes.speculative}",
              n, "paged_span", speculative=sizes.speculative,
              **engine_kwargs)
    # the chunked engine's PREFILL program is the span kernel at
    # Q = chunk (its tick is the plain decode kernel); kernel_leg holds
    # that shape against its oracle
    engine = serve_leg(sizes, model, variables,
                       f"prefill_chunk={sizes.prefill_chunk}", n,
                       "paged_decode", prefill_chunk=sizes.prefill_chunk,
                       **engine_kwargs)
    assert engine.prefill_chunks > n, "no prompt took two chunks"


def prefill_leg(sizes: Sizes, model, variables, **engine_kwargs) -> None:
    """The one-shot prefill's in-place page writes on the device, outside
    the benchmark: an engine with prefix sharing admits a prompt, a
    duplicate of it and a prompt that extends its first blocks (``start``
    at the prompt's end, and on a block edge), float32 and bfloat16 pools;
    every slot's pages then gather to the rows the oracle scatters from
    the same projections (``model.prefill`` + ``scatter_prefill`` on pools
    of zeros, another program: the kernel leg's tolerances), up to the
    slot's length."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.dtypes import bfloat16_compute, use_policy
    from paddle_tpu.serve import DecodeEngine
    from paddle_tpu.serve.kv_cache import gather_pages, scatter_prefill

    t0 = time.perf_counter()
    rng = np.random.RandomState(0)
    bs = sizes.block_size
    first = list(rng.randint(0, sizes.vocab, 2 * bs + bs // 2))
    prompts = [first, list(first),
               first[:2 * bs] + list(rng.randint(0, sizes.vocab, bs + 1))]
    errs: Dict[str, float] = {}
    scat = jax.jit(jax.vmap(scatter_prefill, in_axes=(0, 0, None, None)))
    for kind in ("float32", "bfloat16"):
        with use_policy(bfloat16_compute):
            engine = DecodeEngine(model, variables, max_slots=len(prompts),
                                  block_size=bs, dtype=kind,
                                  share_prefix=True, **engine_kwargs)
            assert engine.prefill_chunk is None
            W = engine.context_width
            ids = np.zeros((len(prompts), W), np.int32)
            for slot, prompt in enumerate(prompts):
                engine.admit(slot, prompt)
                ids[slot, :len(prompt)] = prompt
            _, stacked = jax.jit(lambda v, i: model.apply(
                v, i, method="prefill"))(engine.variables, jnp.asarray(ids))
        assert engine.cache.prefix_hit_blocks >= 4, "nothing was shared"
        tables, lengths = engine.cache.device_tables()
        for name, kv in zip(("k", "v"), stacked):
            pool = engine.cache.pools[name]
            want = scat(jnp.zeros_like(pool), kv.astype(pool.dtype),
                        tables, lengths)
            for layer in range(pool.shape[0]):
                got_rows, want_rows = (
                    np.asarray(gather_pages(p, tables, layer), np.float32)
                    for p in (pool, want))
                for slot, n in enumerate(np.asarray(lengths)):
                    g, w = got_rows[slot, :n], want_rows[slot, :n]
                    assert np.isfinite(g).all()
                    err = relative_error(g, w)
                    assert err <= TOLERANCE[kind], \
                        f"prefill pages {kind}/{name} layer {layer} slot " \
                        f"{slot}: {err:.2e} exceeds {TOLERANCE[kind]:.0e}"
                    errs[kind] = max(errs.get(kind, 0.0), err)
        assert engine.compile_counts()["prefill"] == 1
    log("  one-shot prefill's pages vs the scatter, max relative error: "
        + ", ".join(f"{k} {v:.1e}" for k, v in errs.items())
        + f" ({time.perf_counter() - t0:.1f}s)")


# ---------------------------------------------------------------------------
# kernels against their oracles
# ---------------------------------------------------------------------------

def kernel_leg(sizes: Sizes, seed: int = 0) -> None:
    """``paged_decode_attention`` / ``paged_span_attention`` /
    ``flash_attention`` (forward and gradients) against their float32
    oracles at the shapes the legs above use: ragged lengths, a full
    slot, an inactive slot, f32 / bf16 / int8 pools, with and without
    packed segments. The oracles run at ``highest`` matmul precision (a
    float32 matmul on a TPU is otherwise taken in bf16 passes)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.nn.pallas_attention import (
        flash_attention, paged_decode_attention, paged_reference_attention,
        paged_span_attention, paged_span_reference_attention,
        reference_attention)
    from paddle_tpu.serve.kv_cache import quantize_rows

    t0 = time.perf_counter()
    rng = np.random.RandomState(seed)
    S, H, D, bs = sizes.slots, sizes.heads, sizes.head_dim, sizes.block_size
    MB = sizes.max_len // bs
    W = MB * bs
    N = S * MB + 1

    def normal(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32))

    # every slot owns a distinct run of pool blocks, in shuffled order
    tables = jnp.asarray(
        1 + rng.permutation(S * MB).reshape(S, MB), jnp.int32)
    # two layers' pools; the kernels read the second by index
    layer = jnp.int32(1)
    raw_k, raw_v = normal(2, N, H, bs, D), normal(2, N, H, bs, D)
    pools = {
        "float32": (raw_k, raw_v),
        "bfloat16": (raw_k.astype(jnp.bfloat16), raw_v.astype(jnp.bfloat16)),
        "int8": (quantize_rows(raw_k), quantize_rows(raw_v)),
    }
    # ragged: mid-block, inactive, full, block boundary, then random
    lengths = np.asarray(([5, 0, W, 2 * bs] + list(
        rng.randint(1, W, max(0, S - 4))))[:S], np.int32)
    q1 = normal(S, H, D)
    errs: Dict[str, float] = {}

    def oracle(fn, *args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*args)

    def check(name, kind, got, want):
        assert np.isfinite(np.asarray(got, np.float32)).all(), \
            f"{name}: output is not finite"
        err = relative_error(got, want)
        assert err <= TOLERANCE[kind], \
            f"{name}: {err:.2e} exceeds {TOLERANCE[kind]:.0e}"
        errs[name] = err

    for kind, (pk, pv) in pools.items():
        # the oracles take one layer's pool
        lk, lv = jax.tree_util.tree_map(lambda p: p[layer], (pk, pv))
        rest = (tables, jnp.asarray(lengths))
        got = jax.jit(paged_decode_attention)(q1, pk, pv, *rest, layer)
        want = oracle(paged_reference_attention, q1, lk, lv, *rest)
        check(f"paged_decode/{kind}", kind, got, want)
        assert not np.asarray(got)[lengths == 0].any(), \
            "an inactive slot must read zeros"
        # spans: the speculative verify width over every slot, and one
        # prefill chunk on one slot; ragged starts, a short span, an
        # inactive slot with a stale start
        for Q, slots in ((1 + sizes.speculative, S),
                         (sizes.prefill_chunk, 1)):
            start = np.minimum(lengths[:slots], W - Q).astype(np.int32)
            n = np.full((slots,), Q, np.int32)
            if slots > 2:
                start[1], n[1], n[2] = 7, 0, max(1, Q - 1)
            qs = normal(slots, Q, H, D)
            rest = (tables[:slots], jnp.asarray(start), jnp.asarray(n))
            got = np.asarray(jax.jit(paged_span_attention)(
                qs, pk, pv, *rest, layer))
            want = np.asarray(oracle(paged_span_reference_attention,
                                     qs, lk, lv, *rest))
            live = np.arange(Q)[None, :] < n[:, None]     # rows >= n: pad
            check(f"paged_span/Q{Q}/{kind}", kind, got[live], want[live])
            assert not got[n == 0].any(), \
                "an inactive slot must read zeros"

    # grouped KV heads and a window: ``G`` query heads read each KV head
    # of the same pools (48 and 72 on 8 at the full size), the second case
    # over a slot's last ``window`` positions only: a window that starts
    # inside a page, shorter than some lengths and longer than others
    Q = 1 + sizes.speculative
    for G, window in ((6, None), (9, 4 * bs + 3)):
        qg, qs = normal(S, G * H, D), normal(S, Q, G * H, D)
        start = np.minimum(lengths, W - Q).astype(np.int32)
        n = np.full((S,), Q, np.int32)
        start[1], n[1] = 7, 0
        live = np.arange(Q)[None, :] < n[:, None]
        for kind, (pk, pv) in pools.items():
            lk, lv = jax.tree_util.tree_map(lambda p: p[layer], (pk, pv))
            rest = (tables, jnp.asarray(lengths))
            got = jax.jit(lambda *a: paged_decode_attention(
                *a, window=window))(qg, pk, pv, *rest, layer)
            want = oracle(lambda *a: paged_reference_attention(
                *a, window=window), qg, lk, lv, *rest)
            check(f"paged_decode/G{G}/w{window}/{kind}", kind, got, want)
            assert not np.asarray(got)[lengths == 0].any(), \
                "an inactive slot must read zeros"
            rest = (tables, jnp.asarray(start), jnp.asarray(n))
            got = np.asarray(jax.jit(lambda *a: paged_span_attention(
                *a, window=window))(qs, pk, pv, *rest, layer))
            want = np.asarray(oracle(
                lambda *a: paged_span_reference_attention(
                    *a, window=window), qs, lk, lv, *rest))
            check(f"paged_span/G{G}/w{window}/{kind}", kind, got[live],
                  want[live])

    # flash forward + gradients, bf16 operands as in the train leg
    B, T = 2, sizes.max_len
    q, k, v, w = (normal(B, H, T, D) for _ in range(4))
    bounds = np.sort(rng.randint(1, T, (B, 3)), axis=1)
    seg_ids = 1 + (np.arange(T)[None, :, None]
                   >= bounds[:, None, :]).sum(-1)
    seg_ids[:, -T // 16:] = 0                      # a padded tail
    for segs in (None, jnp.asarray(seg_ids, jnp.int32)):
        tag = "flash/segments" if segs is not None else "flash/causal"
        keep = (np.asarray(segs) > 0)[:, None, :, None] \
            if segs is not None else np.ones((B, 1, T, 1), bool)
        wm = w * keep                              # padding rows: no signal

        def loss(fn, q, k, v):
            return (fn(q, k, v).astype(jnp.float32) * wm).sum()

        qb, kb, vb = (a.astype(jnp.bfloat16) for a in (q, k, v))
        flash = lambda a, b, c: flash_attention(a, b, c, segs, True)
        ref = lambda a, b, c: reference_attention(a, b, c, True,
                                                  segments=segs)
        out = jax.jit(flash)(qb, kb, vb)
        grads = jax.jit(jax.grad(lambda *a: loss(flash, *a),
                                 (0, 1, 2)))(qb, kb, vb)
        f32 = [a.astype(jnp.float32) for a in (qb, kb, vb)]
        out_ref = oracle(ref, *f32)
        grads_ref = oracle(jax.grad(lambda *a: loss(ref, *a), (0, 1, 2)),
                           *f32)
        check(f"{tag}/out", "bfloat16", np.asarray(out, np.float32) * keep,
              np.asarray(out_ref) * keep)
        for g, gr, nm in zip(grads, grads_ref, ("dq", "dk", "dv")):
            check(f"{tag}/{nm}", "bfloat16", g, gr)
    log("  kernels vs oracles, max relative error: " + ", ".join(
        f"{k} {v:.1e}" for k, v in sorted(errs.items())))
    log(f"  kernels: {time.perf_counter() - t0:.1f}s (compile + run)")


# ---------------------------------------------------------------------------
# four devices
# ---------------------------------------------------------------------------

def four_device_leg(sizes: Sizes, one_chip_losses: List[float], devices,
                    **engine_kwargs) -> None:
    """The same path on four devices in the same process: training on
    the Trainer's data-parallel mesh (``data=4``), serving tensor
    parallel (``model=4``, heads over four). Asserts that it really is
    four: shards on four devices, every device holding bytes, and the
    dp losses agreeing with the one-device run of the same seed."""
    import numpy as np
    import jax
    from paddle_tpu.core import mesh as mesh_lib

    assert len(devices) == 4
    t = train_leg(sizes, mesh=mesh_lib.make_mesh({"data": 4},
                                                 devices=devices))
    leaf = jax.tree_util.tree_leaves(t["variables"]["params"])[0]
    assert len(leaf.sharding.device_set) == 4, leaf.sharding
    gap = float(np.abs(np.asarray(t["losses"])
                       - np.asarray(one_chip_losses)).max())
    # same seed, same batches: only the reduction order and bf16
    # rounding differ between one device and four
    assert gap <= 0.05, f"dp=4 losses {t['losses']} vs one device " \
                        f"{one_chip_losses}"
    log(f"  dp=4 vs one device: max loss gap {gap:.4f}")
    mesh = mesh_lib.make_mesh({"model": 4}, devices=devices)
    engine = serve_leg(sizes, t["model"], t["variables"], "tp=4",
                       sizes.variant_requests + sizes.slots, "paged_decode",
                       mesh=mesh, **engine_kwargs)
    pool = jax.tree_util.tree_leaves(engine.cache.k)[0]
    assert len(pool.sharding.device_set) == 4, pool.sharding
    assert pool.addressable_shards[0].data.shape[2] == sizes.heads // 4
    stats = [d.memory_stats() for d in devices]
    if all(s is not None for s in stats):          # the CPU reports none
        used = [s["bytes_in_use"] for s in stats]
        assert all(u > 0 for u in used), f"an idle device: {used}"
        log(f"  bytes in use per device: {used}")


# ---------------------------------------------------------------------------
# the chip run
# ---------------------------------------------------------------------------

def main() -> int:
    import importlib.metadata
    import jax
    import jaxlib
    import paddle_tpu.native
    from paddle_tpu.core import mesh as mesh_lib
    from paddle_tpu.obs import xla_cache
    from paddle_tpu.obs.telemetry import PEAK_FLOPS

    t_start = time.perf_counter()
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    log(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu "
        f"{importlib.metadata.version('libtpu')}, device "
        f"{json.dumps(device)}")
    if dev.platform != "tpu":
        log(f"chip_smoke needs a TPU; jax found {dev.platform!r}")
        return 1
    if dev.device_kind not in PEAK_FLOPS:
        log(f"device kind {dev.device_kind!r} has no entry in "
            f"obs.telemetry.PEAK_FLOPS")
        return 1
    cache_dir = xla_cache.setup()               # before the first compile
    cached = xla_cache.cache_entry_count()
    log(f"compile cache {cache_dir}: {cached} entries at start; native "
        f"packer: {'built with g++' if paddle_tpu.native.available() else 'python fallback'}")

    sizes = FULL
    log(f"[train] {sizes}")
    trained = train_leg(sizes, mesh=mesh_lib.single_device_mesh(dev))
    log("[serve]")
    serve_legs(sizes, trained["model"], trained["variables"])
    log("[prefill]")
    prefill_leg(sizes, trained["model"], trained["variables"])
    log("[kernels]")
    kernel_leg(sizes)
    losses = trained["losses"]
    del trained
    if len(devices) >= 4:
        log("[four devices]")
        four_device_leg(sizes, losses, devices[:4])
    stats = dev.memory_stats() or {}
    log(f"compile cache: {xla_cache.cache_entry_count() - cached} entries "
        f"added; peak device bytes {stats.get('peak_bytes_in_use')}; "
        f"total {time.perf_counter() - t_start:.0f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
