"""The decode engine — two compiled fixed-shape programs serving any
number of concurrent ragged requests.

The framework's static-shapes contract ("Static shapes everywhere",
DESIGN_DECISIONS) is what makes serving latency predictable: a program
that retraces when a request arrives or finishes pays seconds of XLA
compile mid-traffic. So the engine compiles exactly TWO programs and
reuses them for the whole process lifetime:

- **prefill**: at the fixed padded width ``[1, W]`` (``W`` = the cache's
  per-slot context capacity) by default, or — with
  ``prefill_chunk=C`` — at the fixed CHUNK width ``[1, C]``, so a long
  prompt becomes ``ceil(P/C)`` cheap calls the scheduler interleaves
  between decode ticks instead of one monolithic stall (ISSUE 12:
  chunked prefill bounds running slots' TPOT under long admissions).
- the **decode tick** at the fixed slot count ``[S]`` — or, with
  ``speculative=k``, at ``[S, 1+k]``: every tick carries each slot's
  pending token plus ``k`` n-gram self-drafted guesses, one batched
  dispatch verifies all of them, and the host accepts the longest
  draft prefix the model agrees with plus the model's own next token.
  Greedy output is BIT-IDENTICAL to the non-speculative engine by
  construction (each span row is computed by the exact q_len=1 op
  sequence) — speculation only changes how many tokens one memory-bound
  tick retires, never which tokens. The drafted width is a static
  shape, so ``compile_counts()`` stays pinned at {prefill: 1, tick: 1}.

**Copy-on-write prefix sharing** (``share_prefix=True``): admission
looks the prompt up in the cache-resident prefix index and maps every
full-block hit into the slot's table BY REFERENCE (refcounted — zero
new HBM, zero re-scatter); only the divergent tail allocates and
prefills fresh blocks. An exact-duplicate prompt additionally shares
the partial boundary block and forks it (one-block device copy) at the
first divergent write — the OS COW page move at the divergence point.

The KV pools are the tick's DONATED carry: the pool buffers flip between
two XLA allocations instead of reallocating per token. Block tables,
lengths, and the token front are small host-authoritative arrays pushed
per call (bytes, not megabytes — the pools never cross the host
boundary).

Sampling is greedy (argmax) by default — deterministic, which is what
lets the serve tests pin engine output against the training forward
bit-for-bit. ``sampling=SamplingConfig(...)`` switches the tick to
seeded stochastic sampling (temperature / top-k / top-p with per-slot,
per-tick PRNG keys); it composes with sharing, chunked prefill AND
speculation — stochastic verification uses the Leviathan
rejection-sampling rule (PAPERS.md [S3], ISSUE 14): a drafted token
``d`` with filtered target probability ``p(d)`` is accepted with
probability ``p(d)`` (the draft distribution is a point mass, so the
accept ratio ``min(1, p/q)`` reduces to ``p(d)``); on rejection the
token resamples from the residual ``norm(max(p - q, 0))`` — ``p`` with
``d`` excluded — which preserves the target distribution EXACTLY by
the standard [S3] argument. Acceptance randomness rides the same
per-slot ``fold_in`` key tree as plain sampling, so a fixed seed
replays the identical token stream.

**Int8 KV quantization** (``kv_dtype="int8"``, ISSUE 14): the pools
store int8 values plus per-row-per-head scale pages; scatters quantize,
the attention kernels dequantize in VMEM (the XLA path in the gather).
Roughly 3-4x the resident sequences per HBM byte at a measured logit
drift bound — the serving bench gate pins >= 99% greedy token
agreement vs the f32 pool on its gate set. **Radix retention** rides
the prefix cache (see ``kv_cache``): evicted registered blocks park in
a retained LRU and later same-prefix admissions hit them without any
concurrently-resident sharer.

**Tensor-parallel serving** (``mesh=``, ISSUE 15): the same two
programs run sharded over a tp mesh — params placed by the megatron
rule, KV pools split on the HEAD axis (each shard owns ``H/tp`` heads
of every block; int8 scale pages split identically), per-shard
attention over local heads, and the row-parallel out/ffn2 projections
all-reduced back to the replicated residual so the logits assemble on
the existing tp head path. The host side never learns about shards:
one logical block table drives every device's pool, which is why CoW,
retention, speculation, chunking and the scheduler compose unchanged
and the tp=2 engine is token-identical (greedy, f32) to the
single-device one. Capacity accounting (``kv_bytes_per_token``) turns
per-shard, so resident sequences at equal per-device HBM scale with
the mesh.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from .kv_cache import PagedKVCache, write_prefill
from ..core.dtypes import canonicalize, current_policy
from ..nn import pallas_mode
from ..obs.trace import live, traced, tspan
from ..parallel.sharding import tp_constrain, tp_shard_scope

__all__ = ["DecodeEngine", "AdmitProbe", "SamplingConfig"]


@dataclasses.dataclass
class AdmitProbe:
    """Structured admission verdict (ISSUE 11 satellite): WHY a request
    can't start matters to the router — ``"slots"`` clears at the next
    eviction (queue briefly), ``"blocks"`` is KV-pool saturation that can
    persist for a straggler's whole lifetime (prefer another replica, or
    shed), ``"width"`` can never clear (reject). ``ok`` mirrors the old
    boolean ``can_admit`` answer. ``free_blocks`` counts RECLAIMABLE
    capacity (genuinely free + retained-LRU blocks — ISSUE 14: a probe
    on raw free alone undercounts and sheds spuriously under
    retention); ``raw_free_blocks`` keeps the eager-free number and
    ``retained_blocks`` the difference's provenance."""
    ok: bool
    reason: Optional[str]          # None | "width" | "slots" | "blocks"
    blocks_needed: int
    free_blocks: int
    free_slots: int
    raw_free_blocks: int = 0
    retained_blocks: int = 0


@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    """Stochastic decoding knobs (ISSUE 12 satellite). Applied inside
    the compiled tick with a per-slot, per-tick PRNG key
    (``fold_in(fold_in(seed, tick), slot)``) so a fixed seed replays the
    exact token stream — seeded-deterministic, not merely "random".
    Filters compose in the conventional order: temperature scaling,
    then top-k truncation, then top-p (nucleus) truncation."""
    temperature: float = 1.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    seed: int = 0

    def validate(self, vocab: int) -> None:
        if not self.temperature > 0:
            raise ValueError(f"temperature must be > 0 (greedy is "
                             f"sampling=None), got {self.temperature}")
        if self.top_k is not None and not 1 <= self.top_k <= vocab:
            raise ValueError(f"top_k must be in [1, {vocab}], "
                             f"got {self.top_k}")
        if self.top_p is not None and not 0 < self.top_p <= 1:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")


def _filter_logits(cfg: SamplingConfig, logits):
    """Temperature -> top-k -> top-p filtering over the LAST axis (any
    leading shape): the filtered logits define the target distribution
    ``p`` both plain sampling and the [S3] accept/resample rule draw
    from. Top-k keeps the k highest logits; top-p keeps the smallest
    descending-probability set whose mass reaches p (the head token
    always survives both)."""
    x = logits.astype(jnp.float32) / cfg.temperature
    if cfg.top_k is not None:
        kth = jnp.sort(x, axis=-1)[..., -cfg.top_k][..., None]
        x = jnp.where(x >= kth, x, -jnp.inf)
    if cfg.top_p is not None:
        sorted_x = jnp.sort(x, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_x, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep entries whose PRECEDING cumulative mass is < p (the
        # first token always survives); find the cutoff logit value
        keep = (cum - probs) < cfg.top_p
        cutoff = jnp.min(jnp.where(keep, sorted_x, jnp.inf), axis=-1)
        x = jnp.where(x >= cutoff[..., None], x, -jnp.inf)
    return x


def _sample_tokens(cfg: SamplingConfig, logits, keys):
    """Traced sampler: ``logits [S, V]``, ``keys [S, 2]`` -> ``[S]``
    int32 draws from the filtered target distribution."""
    x = _filter_logits(cfg, logits)
    return jax.vmap(jax.random.categorical)(keys, x).astype(jnp.int32)


def _resolve_attention(attention: str) -> str:
    """``"auto"`` is the Pallas paged kernel on a TPU — always: a kernel
    Mosaic refuses raises, it never gives way to ``"xla"``, whose O(W^2)
    broadcast per slot is a test reference — and the bit-exact XLA
    gather path where the kernels would only be interpreted
    (:func:`paddle_tpu.nn.pallas_mode.interpret`, the same rule as the
    flash kernels' ``interpret=None``)."""
    if attention == "auto":
        return "xla" if pallas_mode.interpret() else "paged"
    if attention not in ("paged", "xla"):
        raise ValueError(f"attention must be 'auto'|'paged'|'xla', "
                         f"got {attention!r}")
    return attention


class DecodeEngine:
    """Compiled serving runtime for any model that states its paged
    cache and has the serving entry points.

    What the engine asks of a model, and all it may assume:

    - ``model.cache_spec()``: ``{"layers": L, "pools": {name: row shape},
      "counters": {name: shape}}`` (``counters`` optional). A pool is the
      paged state ONE token leaves in ONE layer; the cache allocates each
      as ``[L, num_blocks, *row[:-1], block_size, row[-1]]``.
      :class:`~paddle_tpu.models.TransformerLM` declares ``k`` and ``v``
      rows of ``[heads, head size]``,
      :class:`~paddle_tpu.models.LatentMoELM` one ``latent`` row.
      A model whose layers are of several KINDS declares ``{"groups":
      {group: {"layers": n, "pools": {...}, "window": w}}}`` instead
      (``window`` optional): the pools are then named ``<group>/<name>``,
      each group is sized apart, a window group holds ``ceil(w /
      block_size) + 1`` blocks a slot whatever the context
      (``serve/kv_cache.py``), and ``tables`` below is a dict, a table a
      group (:class:`~paddle_tpu.models.WindowMoELM`: ``full`` and
      ``window`` groups of ``k`` / ``v`` rows). A group declared with
      ``"state": True`` is a STATE group: a pool ``[n, max_slots,
      *row]`` a slot keeps whatever its length, whose "table" is the
      slots a call's rows are (:class:`~paddle_tpu.models.ConvMoELM`: a
      ``conv`` group of its short convolutions' last inputs).
    - ``decode_step(token, kv, positions, active, attn_impl=)`` and
      ``decode_span(tokens, kv, start, n, active, attn_impl=,
      write_from=)`` with ``kv = (*pools in declared order, tables)``,
      returning ``(logits, kv')`` and, where counters are declared, a
      dict of them third. The entry points carry every layer's pool
      WHOLE, write their rows IN PLACE and read pages by layer index
      (``serve/kv_cache.py:write_token``): the engine donates the pools
      and a program that copied one would not fit
      (``tests/test_chip_lowering.py`` holds both models to it).
    - ``model.serving_variables(variables)``: the tree the entry points
      run on, from the placed training tree. Whatever a program would
      derive from the weights alone, every call, the model derives here,
      once (``TransformerLM``: the blocks' stack and the compute-type
      cast); the leaves it does not change come back as the objects they
      were, and a model with nothing to derive returns its argument.
    - ``model.emb.vocab`` and ``model.max_len`` (the longest sequence a
      slot's table may cover; a position table's length, or only a bound
      where positions are rotary).

    The host side (block tables, allocator, prefix cache, copy-on-write,
    the scheduler) never learns what a row holds. What is defined for
    ``k`` / ``v`` rows only refuses any other declaration at build:
    ``kv_dtype="int8"``, ``mesh=``, the one-shot prefill;
    ``export_slot`` / ``adopt_slot`` (and with them
    ``serve/transport.py``'s wire format) when called. A WINDOW group
    refuses besides, at build: prefix sharing with its copy-on-write,
    speculation, and a prefill chunk longer than the window; a STATE
    group refuses prefix sharing and speculation at build, and no path
    keeps a slot's pages without its state: an eviction frees both (a
    prefill from position 0 starts from a zero state) and the page
    export of a handoff refuses it.

    **Starved time** (``starved_s``, cumulative seconds beside ``ticks``
    and ``tokens_generated``): each compiled call takes the pools the call
    before it wrote, so from the moment a drain's wait returns (a tick's,
    or a prompt's last prefill call's) none of the engine's programs is in
    flight until the next compiled call (``_tick_fn``, ``_prefill_fn``, or
    ``_cow_fn`` in the copy-on-write guard) returns; ``adopt_slot``, whose
    page import writes the pools, ends a stretch before it and opens none.
    Every such stretch adds to ``starved_s``: always on, two clock reads a
    program. An operator reads it as a share of wall time,
    ``d(starved_s) / d(wall)``: the host-bound share of the chip, with no
    profiler attached. It cannot tell a slow host from a scheduler with no
    work: an open-loop lull with nothing queued is starved time too. It
    counts as starved what is small and is not the engine's programs: the
    eager copies and key that ``prefill_dispatch`` makes before its
    compiled call, and work another user of the process puts on the chip.
    A stretch ends when the compiled call RETURNS, and the chip may start
    on the program a little before that. Where spans are live
    (``obs/trace.py:live``) each stretch is also a retroactive ``starved``
    span with facts ``after`` (``tick`` or ``prefill``) and ``by``
    (``tick``, ``prefill``, ``cow`` or ``import``), from the start of the
    drain's ``tick_fetch`` / ``prefill_fetch`` to the end of the next
    ``tick_dispatch`` / ``prefill_dispatch`` (a ``cow`` one ends inside
    ``tick_stage``), so a device trace finds every stretch from those
    annotations; ``obs/trace.py:starved_by_span`` names the host work
    inside them.

    Args:
      model: a TransformerLM (any training config: a training
        checkpoint serves as it is) or a LatentMoELM.
      variables: the model's variables dict (training checkpoint or
        ``load_inference_model`` output). It stays the caller's. What the
        engine holds as ``self.variables``, and hands to both programs,
        is ``model.serving_variables(...)`` of it, made once at build
        after placement: a TransformerLM's blocks stacked for the layer
        scan with the products' operands in the compute type of the
        policy the engine is BUILT under (``self.policy``; a program
        traced under another raises); a LatentMoELM's bfloat16 leaves as
        they are. The engine keeps no other copy of the weights, so a
        caller that drops its tree frees the float32 matrices.
      max_slots: decode-tick batch width S — the max concurrent
        sequences. Fixed at compile time; empty slots are masked lanes.
      block_size: KV tokens per pool block. Small blocks waste less on
        ragged tails but cost more gather indirection; 16 is the
        conventional sweet spot (DESIGN_DECISIONS PR-9).
      num_blocks: pool size. Default sizes the pool for full residency
        (every slot at full context) — shrink it to test admission
        backpressure.
      max_blocks_per_seq: per-slot table width; the per-slot context
        capacity is ``max_blocks_per_seq * block_size`` (defaults to
        ``model.max_len // block_size``, and must keep the capacity
        within ``model.max_len`` — positions are embedded).
      attention: ``"auto" | "paged" | "xla"`` — see
        :func:`_resolve_attention`. The span path (speculation /
        chunked prefill) follows the same choice: the multi-query paged
        kernel on TPU, the bit-exact XLA gather path elsewhere
        (ISSUE 14).
      share_prefix: copy-on-write physical block sharing between
        resident sequences with a common prompt prefix (default ON —
        the PagedAttention production win, ISSUE 12 — wherever the
        cache can share: a model with a window group gets it OFF by
        default and refuses True).
      retain_prefix: RadixAttention-style retention (ISSUE 14, needs
        ``share_prefix``): evicted registered blocks park in a
        retained LRU (lazily reclaimed under pool pressure) so
        SEQUENTIAL same-prefix requests hit too, not just
        concurrently-resident ones.
      speculative: number of n-gram self-drafted tokens verified per
        tick (0 = off). Greedy verification is lossless by
        construction; with ``sampling`` the [S3] rejection-sampling
        rule keeps the output distribution exact.
      prefill_chunk: prefill chunk width C (None = legacy one-shot
        full-width prefill). Long prompts prefill in ``ceil(P/C)``
        calls the scheduler interleaves between decode ticks.
      sampling: a :class:`SamplingConfig` for stochastic decoding
        (None = greedy).
      telemetry: optional :class:`paddle_tpu.obs.Telemetry`; the engine
        emits one ``kind="decode_tick"`` record per tick (dispatch wall,
        active slots, tokens/sec, sharing/speculation/retention
        counters, ``kv_bytes_per_token``/``quant_dtype``) and the
        scheduler adds per-request records through the same object.
      dtype: pool dtype, a dtype or its name (``"bfloat16"``: what a
        JSON file can hold). f32 default matches the projections' f32
        accumulation under both the f32 and bf16-compute policies.
      kv_dtype: ``None``/``"f32"`` (pools at ``dtype``) or ``"int8"`` —
        quantized pools with per-row-per-head scale pages (ISSUE 14):
        ~4x fewer HBM bytes per resident token, dequantized in-kernel.
      mesh: optional ``jax.sharding.Mesh`` carrying a ``tp_axis`` axis
        (ISSUE 15): the engine's two compiled programs run TENSOR
        PARALLEL over it — params placed by the megatron
        ``param_sharding`` rule, KV pools sharded on the head axis
        (each shard holds ``H/tp`` heads of every block, int8 scale
        pages split identically), attention + MLP as the tp-sharded
        forward with the out/ffn2 all-reduce assembling the replicated
        residual and logits. The HOST side is shard-oblivious: one
        logical block table, so CoW forks, quantized scatters,
        retention, speculation and the scheduler/fleet compose
        unchanged, and ``compile_counts()`` stays {prefill: 1, tick: 1}.
        ``mesh=None`` (default) is the single-device engine, unchanged.
      param_sharding: with ``mesh=``, the parameter placement — a
        :class:`~paddle_tpu.parallel.ShardingRules` or a PartitionSpec
        pytree (default: :func:`~paddle_tpu.parallel.megatron_sp_rules`,
        the same layout the training tp paths use, so tp-trained
        checkpoints serve with zero resharding).
      tp_axis: the mesh axis name carrying the tensor-parallel degree
        (default ``"model"``, the framework's standard axis).
    """

    @traced("engine_init")
    def __init__(self, model, variables, *, max_slots: int = 4,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 max_blocks_per_seq: Optional[int] = None,
                 attention: str = "auto",
                 share_prefix: Optional[bool] = None,
                 retain_prefix: bool = True,
                 speculative: int = 0,
                 prefill_chunk: Optional[int] = None,
                 sampling: Optional[SamplingConfig] = None,
                 telemetry=None, dtype=jnp.float32,
                 kv_dtype: Optional[str] = None,
                 mesh=None, param_sharding=None, tp_axis: str = "model"):
        self.model = model
        self.telemetry = telemetry
        # optional Tracer (ISSUE 17): assigned by the fleet/replica when
        # request tracing is on. With None the engine's spans are live
        # while a jax.profiler session is active (obs.trace.live) and
        # cost that one test a call site otherwise
        self.tracer = None
        # optional metrics registry handle (ISSUE 19): assigned by the
        # fleet (replica-scoped facade) or the replica child (its local
        # hub) — same contract as tracer, None costs one attribute test
        self.metrics = None
        self._metrics_tick_counters: Dict[str, int] = {}
        self.attention = _resolve_attention(attention)
        if speculative < 0:
            raise ValueError(f"speculative must be >= 0, "
                             f"got {speculative}")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, "
                             f"got {prefill_chunk}")
        if sampling is not None:
            sampling.validate(model.emb.vocab)
        self.speculative = int(speculative)
        self.prefill_chunk = prefill_chunk
        self.sampling = sampling
        # the model DECLARES the paged state it keeps (cache_spec):
        # named pools, each the shape of one token's row in one layer,
        # and the counters its entry points return beside the pools
        spec = model.cache_spec()
        groups = spec.get("groups")
        if groups:
            self.pool_names = tuple(f"{g}/{n}" for g, decl in groups.items()
                                    for n in decl["pools"])
            num_layers = None
        else:
            self.pool_names = tuple(spec["pools"])
            num_layers = int(spec["layers"])
        self.counter_names = tuple(spec.get("counters", ()))
        kv_pools = self.pool_names == ("k", "v")
        windows = [int(g["window"]) for g in (groups or {}).values()
                   if g.get("window")]
        states = [g for g, decl in (groups or {}).items()
                  if decl.get("state")]
        if share_prefix is None:
            share_prefix = not (windows or states)
        # what has not been carried over to other pools, to a window
        # group's ring or to a state group, fails here and not in a
        # compiled program
        refused = []
        if not kv_pools:
            refused += [
                (kv_dtype == "int8", "kv_dtype='int8'"),
                (mesh is not None, "mesh="),
                (prefill_chunk is None,
                 "the one-shot prefill (prefill_chunk=None)")]
        if windows or states:
            refused += [
                (share_prefix, "share_prefix=True (prefix sharing and "
                 "copy-on-write)"),
                (speculative > 0, "speculative > 0")]
        if windows:
            refused += [
                (prefill_chunk is not None and prefill_chunk > min(windows),
                 f"a prefill chunk longer than the window {min(windows)}")]
        for given, what in refused:
            if given:
                raise NotImplementedError(
                    f"{what} is defined for the k / v pools of multi-head "
                    f"attention only; {type(model).__name__} declares "
                    f"{list(self.pool_names)}"
                    + (f" with a window group of {min(windows)}: a ring a "
                       f"slot, which no other slot shares, a rejected draft "
                       f"cannot be taken out of and a longer chunk wraps"
                       if windows else "")
                    + (f" with the state group(s) {states}: a slot's state "
                       f"at the end of its positions, which a shared page "
                       f"does not carry and a rejected draft cannot be "
                       f"taken out of" if states else "")
                    + ": int8 rows, head sharding, the prefill scatter, "
                    "export_slot / adopt_slot and the transport's wire "
                    "format know [heads, head size] rows in ONE pool "
                    "group")
        dtype = canonicalize(dtype)
        num_heads, head_dim = spec["pools"]["k"] if kv_pools else (1, None)
        # tensor-parallel mesh (ISSUE 15): resolve the tp degree, place
        # the params by the megatron rule, and shard the pools on the
        # head axis. All of it is PLACEMENT — the traced program bodies
        # below are identical either way (shard-in-scope pins the layout
        # at trace time; the SPMD partitioner inserts the collectives).
        self.mesh = mesh
        self.tp_axis = tp_axis
        if mesh is not None:
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            if tp_axis not in sizes:
                raise ValueError(f"mesh has no {tp_axis!r} axis "
                                 f"(axes: {list(sizes)})")
            self.tp_degree = int(sizes[tp_axis])
            if num_heads % self.tp_degree:
                raise ValueError(
                    f"num_heads {num_heads} must divide by tp degree "
                    f"{self.tp_degree} (head-axis KV sharding)")
            from ..parallel.sharding import shard_tree
            if param_sharding is None:
                from ..parallel.megatron import megatron_sp_rules
                # thread tp_axis through: a mesh whose tp axis is not
                # named "model" must get matching default specs
                param_sharding = megatron_sp_rules(model_axis=tp_axis)
            placed = shard_tree(mesh, variables, param_sharding)
        else:
            self.tp_degree = 1
            # one device, one placement: params and pools committed
            # alike, so that the pools a program returns hash like the
            # ones it was given. jax keys its trace cache on placement;
            # params arriving as a Trainer left them (on its mesh) beside
            # uncommitted pools would trace each program twice.
            leaf = jax.tree_util.tree_leaves(variables)[0]
            dev = (next(iter(leaf.devices())) if isinstance(leaf, jax.Array)
                   else jax.devices()[0])
            placement = jax.sharding.SingleDeviceSharding(dev)
            placed = jax.device_put(variables, placement)
        # What the programs derive from the weights alone is derived
        # HERE, once: the model turns the placed tree into the tree its
        # entry points run on (a TransformerLM: the blocks stacked for the
        # layer scan, the products' operands in the policy's compute
        # type; one jitted program of the model's, not an entry point).
        # Before the pools exist, so that the caller's tree, the result
        # and the pools never stand beside that program's temporaries.
        # The engine holds the RESULT and no other copy of the weights;
        # it is right for this policy only, which the traced bodies check.
        self.policy = current_policy()
        with tspan(self.tracer, "engine_prepare"):
            self.variables = model.serving_variables(placed)
        del placed
        if max_blocks_per_seq is None:
            max_blocks_per_seq = max(1, model.max_len // block_size)
        if max_blocks_per_seq * block_size > model.max_len:
            raise ValueError(
                f"slot capacity {max_blocks_per_seq * block_size} exceeds "
                f"model.max_len={model.max_len} (positions are embedded)")
        if num_blocks is None:
            num_blocks = max_slots * max_blocks_per_seq + 1   # + null block
        self.cache = PagedKVCache(
            num_layers, num_heads, head_dim, num_blocks, block_size,
            max_slots=max_slots, max_blocks_per_seq=max_blocks_per_seq,
            dtype=dtype, share_prefix=share_prefix, kv_dtype=kv_dtype,
            retain_prefix=retain_prefix, tp_degree=self.tp_degree,
            row_shapes=None if kv_pools or groups else dict(spec["pools"]),
            groups=groups)
        if mesh is not None:
            self.cache.shard_pools(mesh, tp_axis)
        else:
            self.cache.pools = jax.device_put(self.cache.pools, placement)
        self.max_slots = max_slots
        # host-authoritative slot state beside the cache's tables/lengths
        self.active = np.zeros((max_slots,), bool)
        self.tokens = np.zeros((max_slots,), np.int32)   # next to decode
        # per-slot token history (prompt + accepted generations): the
        # n-gram self-drafter's corpus — tiny host lists, always kept.
        # The drafter's lookup is incremental: per-slot maps of bigram/
        # token -> (latest index, previous-latest index), maintained on
        # append, so each proposal is O(k) instead of rescanning the
        # history per tick
        self.history: List[List[int]] = [[] for _ in range(max_slots)]
        self._bigram_idx: List[Dict] = [{} for _ in range(max_slots)]
        self._unigram_idx: List[Dict] = [{} for _ in range(max_slots)]
        self._tick_counters: Dict[str, int] = {}
        # chunked-prefill cursors: slot -> (prompt, cursor, shared_len)
        self._prefilling: Dict[int, Dict[str, Any]] = {}
        self.ticks = 0
        self.tokens_generated = 0
        # seconds the chip had nothing queued (class docstring); the open
        # stretch: what drained last and when its wait returned (ns)
        self.starved_s = 0.0
        self._starved_since: Optional[tuple] = None
        self.prefill_chunks = 0          # cumulative chunk calls
        self.draft_proposed = 0          # cumulative drafted tokens
        self.draft_accepted = 0          # cumulative accepted drafts
        # of a model that counts them (``expert_tokens`` among its
        # counters): (token, expert) pairs its held experts received, and
        # held experts with at least one token, summed over calls
        self.expert_pairs = 0
        self.expert_hits = 0
        # per-slot attribution for request-level telemetry
        self.slot_stats: List[Dict[str, int]] = [
            {} for _ in range(max_slots)]
        # what the last tick retired per slot (list of accepted tokens;
        # [tok] for the non-speculative tick) — the scheduler's view
        self.last_accepted: Dict[int, List[int]] = {}

        W = self.cache.context_width
        attn_impl = self.attention
        K1 = 1 + self.speculative
        cfg = self.sampling

        def first_token(last_logits, key):
            if cfg is None:
                return jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
            return _sample_tokens(cfg, last_logits[None], key[None])[0]

        names, counted = self.pool_names, bool(self.counter_names)

        def run(method, variables, pools, tables, *args, **kw):
            """A serving entry point of the model on the declared pools:
            ``kv`` goes in as ``(*pools in declared order, tables)`` and
            comes back alike; a model that declares counters returns
            them third. Returns ``(logits, pools, counters)``."""
            out = model.apply(variables, args[0],
                              (*(pools[k] for k in names), tables),
                              *args[1:], method=method, **kw)
            return (out[0], dict(zip(names, out[1][:-1])),
                    out[2] if counted else {})

        def tail(counters):
            """What a program returns after its tokens: the model's
            counters, where it declares any, fetched with the tokens."""
            return (counters,) if counted else ()

        if prefill_chunk is None:
            def prefill_fn(variables, pools, ids, length,
                           start, table, key):
                # ids [1, W] padded; length/start [1]; table [1, MB]
                logits, (ks, vs) = model.apply(variables, ids,
                                               method="prefill")
                pages_k = write_prefill(pools["k"], ks, table, length, start)
                pages_v = write_prefill(pools["v"], vs, table, length, start)
                last = jnp.take_along_axis(
                    logits, (length - 1)[:, None, None], axis=1)[0, 0]
                return {"k": pages_k, "v": pages_v}, first_token(last, key)
        else:
            C = prefill_chunk

            def prefill_fn(variables, pools, ids, start, n,
                           write_from, table, key):
                # ids [1, C]: tokens at positions start..start+n-1;
                # rows >= n are padding; scatter floored at write_from
                # (shared-prefix rows are co-owned — never rewritten)
                logits, pools, counters = run(
                    "decode_span", variables, pools, table, ids, start, n,
                    jnp.ones((1,), bool), attn_impl=attn_impl,
                    write_from=write_from)
                last = jnp.take_along_axis(
                    logits, (n - 1)[:, None, None], axis=1)[0, 0]
                return (pools, first_token(last, key)) + tail(counters)

        if self.speculative == 0:
            def tick_fn(variables, pools, tables, lengths,
                        tokens, active, keys):
                logits, pools, counters = run(
                    "decode_step", variables, pools, tables, tokens,
                    lengths, active, attn_impl=attn_impl)
                if cfg is None:
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                else:
                    nxt = _sample_tokens(cfg, logits, keys)
                return (pools, nxt[:, None]) + tail(counters)
        elif cfg is None:
            def tick_fn(variables, pools, tables, lengths,
                        tokens, n, active):
                # tokens [S, 1+k]: pending + drafts; ONE span dispatch
                # verifies every draft (greedy argmax per row)
                logits, pools, counters = run(
                    "decode_span", variables, pools, tables, tokens,
                    lengths, n, active, attn_impl=attn_impl)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (pools, nxt) + tail(counters)        # [S, 1+k]
        else:
            def tick_fn(variables, pools, tables, lengths,
                        tokens, n, active, keys):
                # stochastic speculation, the [S3] rejection rule: for
                # draft row j the proposal distribution is a point mass
                # at tokens[:, j+1], so accept with prob p_j(draft) and
                # resample rejections from p_j with the draft excluded
                # (= norm(max(p - q, 0))) — distribution-preserving by
                # construction. All three verdict arrays are computed in
                # ONE dispatch; the host walks the accept prefix.
                logits, pools, counters = run(
                    "decode_span", variables, pools, tables, tokens,
                    lengths, n, active, attn_impl=attn_impl)
                x = _filter_logits(cfg, logits)     # [S, 1+k, V]
                p = jax.nn.softmax(x, axis=-1)
                Q = x.shape[1]
                # per-row keys: fold the row index into the slot key,
                # then a role constant (0 = accept-u, 1 = resample,
                # 2 = bonus sample) — seeded-deterministic replay
                rows = jnp.arange(Q)
                rk = jax.vmap(lambda key: jax.vmap(
                    lambda r: jax.random.fold_in(key, r))(rows))(keys)
                role = lambda c: jax.vmap(jax.vmap(
                    lambda kk: jax.random.fold_in(kk, c)))(rk)
                u = jax.vmap(jax.vmap(jax.random.uniform))(role(0))
                drafts = tokens[:, 1:]              # [S, k]
                p_draft = jnp.take_along_axis(
                    p[:, :-1], drafts[..., None], axis=-1)[..., 0]
                accept = u[:, :-1] < p_draft        # [S, k]
                res_x = jnp.where(
                    jax.nn.one_hot(drafts, x.shape[-1], dtype=bool),
                    -jnp.inf, x[:, :-1])
                resample = jax.vmap(jax.vmap(jax.random.categorical))(
                    role(1)[:, :-1], res_x).astype(jnp.int32)
                bonus = jax.vmap(jax.vmap(jax.random.categorical))(
                    role(2), x).astype(jnp.int32)   # [S, 1+k]
                return (pools, accept, resample, bonus) + tail(counters)

        # shard-in-scope wrapping (ISSUE 15): with a mesh, every traced
        # body runs inside tp_shard_scope (the attention layer pins
        # head-sharded projections/pools, the model pins replicated
        # residual/logits). _in_scope is the ONE place scope entry
        # happens; without a mesh it is the identity and every
        # tp_constrain below no-ops, so the single-device trace is
        # byte-identical.
        def _in_scope(fn):
            if self.mesh is None:
                return fn

            def wrapped(*args):
                with tp_shard_scope(self.mesh, self.tp_axis):
                    return fn(*args)
            return wrapped

        # The compiled programs' RETURNED pools are constrained back to
        # the head-sharded input placement — without the output pin the
        # partitioner may pick a different pool layout, which both
        # breaks donation and retraces the next call on the changed
        # input sharding (the no-retrace invariant would die quietly).
        def _pin_pools(fn):
            def pinned(*args):
                pools, *rest = fn(*args)
                return (jax.tree_util.tree_map(
                    lambda o: tp_constrain(o, 2), pools), *rest)
            return pinned

        # The weights were prepared under the build's policy: a body traced
        # under another would multiply operands cast for the first by
        # activations cast for the second, quietly. (A policy is trace-time
        # state and no part of jit's cache key: once traced, a program runs
        # as traced whatever the caller's policy.)
        def _same_policy(fn):
            def checked(*args):
                if current_policy() != self.policy:
                    raise RuntimeError(
                        f"this engine prepared its weights under "
                        f"{self.policy} and is being traced under "
                        f"{current_policy()}: build it, warm it up and "
                        f"make its first calls inside one use_policy")
                return fn(*args)
            return checked

        # donate the KV pools: both programs write into the buffers they
        # were handed and return them. The tick's layer scan carries the
        # pools and writes rows; the one-shot prefill's layer loop carries
        # each and writes whole pages (kv_cache.write_prefill); nothing in
        # either has a pool-sized result but those writes
        # (tests/test_chip_lowering.py). A quantized pool's one-shot
        # prefill still scatters, and XLA copies the pool round it
        self._prefill_fn = jax.jit(
            _same_policy(_in_scope(_pin_pools(prefill_fn))),
            donate_argnums=(1,))
        self._tick_fn = jax.jit(
            _same_policy(_in_scope(_pin_pools(tick_fn))),
            donate_argnums=(1,))
        # COW block copy: [L, H, bs, hd] pages move pool-internally, one
        # tiny donated program (not an engine entry point — not counted
        # in compile_counts, traced once for the process lifetime).
        # tree_map covers the quantized (values, scales) tuple pools —
        # a fork copies the scale page with its value page. Sharded
        # pools copy shard-locally (the block axis is unsharded, the
        # head axis untouched) — the output pin keeps the carry layout.
        def _cow(pages, src, dst):
            out = jax.tree_util.tree_map(
                lambda p: p.at[:, dst].set(p[:, src]), pages)
            return tp_constrain(out, 2)

        self._cow_fn = jax.jit(_in_scope(_cow), donate_argnums=(0,))
        self._zero_keys = jnp.zeros((max_slots, 2), jnp.uint32)
        seed = sampling.seed if sampling is not None else 0
        self._tick_keys = jax.jit(lambda t: jax.vmap(
            lambda s: jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(seed), t), s))(
                    jnp.arange(max_slots)))
        self._W = W
        self._K1 = K1

    # -- introspection -----------------------------------------------------

    @property
    def context_width(self) -> int:
        return self._W

    def compile_counts(self) -> Dict[str, int]:
        """Distinct traced programs per entry point — the no-retrace
        invariant is both == 1 after warmup, across any admit/evict
        churn AND with speculation/chunking/sharing on (drafted width
        and chunk width are static shapes; the bench serving gate
        asserts it)."""
        return {"prefill": int(self._prefill_fn._cache_size()),
                "tick": int(self._tick_fn._cache_size())}

    # -- warmup (ISSUE 16) -------------------------------------------------

    @traced("engine_warmup")
    def warmup(self) -> Dict[str, Any]:
        """Pay both programs' compiles NOW, before the first request.

        Executes each compiled entry point once with all-inactive dummy
        operands — every slot masked off, lengths 0, zero ids — built
        with the exact aval construction of the real call sites, so the
        jit cache ends at ``{prefill: 1, tick: 1}`` and the first real
        request retraces nothing. Executing (rather than AOT
        ``lower().compile()``) is what populates the jit cache AND the
        persistent compilation cache in one move; it is numerically
        harmless because pool contents only matter where an active
        slot's table+length mark them valid (the eviction rule: stale
        pool contents are finite and always length-masked — warmup's
        stray writes land in block 0, which the first real prefill
        rewrites before any read), and it consumes no entropy — the
        PRNG keys fold stateless counters that warmup leaves untouched,
        so warmed and unwarmed engines emit identical token streams.

        With :mod:`~paddle_tpu.nn.autotune` enabled, each program's
        timed warmup registers under the engine's shape key (the
        program-level analog of a kernel's block entry — this is where
        the paged/span programs' grids get their cache row): a restarted
        replica with a populated cache reports the hit and pays zero
        trials. Returns the startup breakdown the replica child ships in
        its hello/heartbeat payloads."""
        assert not self.active.any() and not self._prefilling, \
            "warmup() must run before any admission (fresh engine)"
        from ..nn import autotune
        from ..obs import xla_cache
        t0 = time.perf_counter()
        xla_before = xla_cache.cache_entry_count()
        trials_before = autotune.stats()["trials"]
        timings: Dict[str, float] = {}

        def _prefill_once():
            out = self._prefill_fn(*self._prefill_args())
            # donated pools: the engine's carry is the returned pools
            self.cache.pools = out[0]
            return out[1]

        def _tick_once():
            tables, lengths = self.cache.device_tables()
            if self.speculative == 0:
                keys = (self._zero_keys if self.sampling is None
                        else self._tick_keys(self.ticks))
                out = self._tick_fn(
                    self.variables, self.cache.pools, tables,
                    lengths, jnp.asarray(self.tokens),
                    jnp.asarray(self.active), keys)
            elif self.sampling is not None:
                out = self._tick_fn(
                    self.variables, self.cache.pools, tables,
                    lengths, jnp.zeros((self.max_slots, self._K1),
                                       jnp.int32),
                    jnp.zeros((self.max_slots,), jnp.int32),
                    jnp.asarray(self.active), self._tick_keys(self.ticks))
            else:
                out = self._tick_fn(
                    self.variables, self.cache.pools, tables,
                    lengths, jnp.zeros((self.max_slots, self._K1),
                                       jnp.int32),
                    jnp.zeros((self.max_slots,), jnp.int32),
                    jnp.asarray(self.active))
            self.cache.pools = out[0]
            return out[1]

        def _measured(name, fn):
            t = time.perf_counter()
            if autotune.is_enabled():
                key = autotune.make_key(
                    f"serve_{name}",
                    shape=(self.max_slots, self._W, self._K1,
                           self.cache.block_size, self.cache.num_blocks),
                    dtype=self.cache.quant_dtype,
                    extra=(self.speculative,
                           int(self.sampling is not None),
                           self.prefill_chunk, self.attention))
                before = autotune.stats()["trials"]
                autotune.choose(f"serve_{name}", key=key,
                                candidates=[{}], runner=fn, default={})
                if autotune.stats()["trials"] == before:
                    fn()    # cache hit skipped the timed trial — still
                    #         warm this process's jit cache
            else:
                fn()
            jax.block_until_ready(self.cache.pools)
            timings[name] = time.perf_counter() - t

        _measured("prefill", _prefill_once)
        _measured("tick", _tick_once)
        wall = time.perf_counter() - t0
        trials = autotune.stats()["trials"] - trials_before
        added = xla_cache.cache_entry_count() - xla_before
        xla_hit = (None if xla_cache.active_dir() is None
                   else added == 0)
        report = {
            "prefill_s": round(timings["prefill"], 6),
            "tick_s": round(timings["tick"], 6),
            "wall_s": round(wall, 6),
            "autotune_trials": trials,
            "autotune_cache_hit": (None if not autotune.is_enabled()
                                   else trials == 0),
            "xla_cache_entries_added": added,
            "xla_cache_hit": xla_hit,
            "compile_counts": self.compile_counts(),
            # the tree both programs take, as engine build prepared it
            "prepared_bytes": sum(
                leaf.nbytes
                for leaf in jax.tree_util.tree_leaves(self.variables)),
        }
        if self.telemetry is not None:
            self.telemetry.record_compile(
                "serve_warmup", wall, cache_hit=xla_hit,
                autotune_trials=trials,
                meta={"warmup": True,
                      "prefill_s": report["prefill_s"],
                      "tick_s": report["tick_s"]})
        return report

    def free_slots(self) -> List[int]:
        return [s for s in range(self.max_slots)
                if not self.active[s] and s not in self._prefilling]

    def admit_probe(self, total_len: int,
                    include_slots: bool = True) -> AdmitProbe:
        """Structured admission check for a sequence that may grow to
        ``total_len`` tokens (prompt + generation budget): the first
        failing constraint, in never-clears-first order — ``"width"``
        (exceeds slot capacity), ``"slots"`` (no free decode lane;
        skipped with ``include_slots=False`` for callers that manage
        slots themselves, like the scheduler), ``"blocks"`` (KV pool
        can't cover the worst-case reservation). Deliberately ignores
        prefix-cache hits: the probe is the conservative no-sharing
        bound, so an admitted request can never strand mid-decode even
        if every co-owner forks. The blocks check runs against
        RECLAIMABLE capacity — free plus retained-LRU blocks (ISSUE 14:
        retained blocks are one lazy reclaim away from free; probing
        raw ``num_free`` alone would report ``"blocks"`` backpressure,
        and shed, against capacity the pool actually has)."""
        blocks_needed = self.cache.blocks_needed(total_len)
        free_slots = len(self.free_slots())
        reclaimable = self.cache.free_blocks      # free + retained
        if total_len > self._W:
            reason = "width"
        elif include_slots and free_slots == 0:
            reason = "slots"
        elif blocks_needed > reclaimable:
            reason = "blocks"
        else:
            reason = None
        return AdmitProbe(ok=reason is None, reason=reason,
                          blocks_needed=blocks_needed,
                          free_blocks=reclaimable,
                          free_slots=free_slots,
                          raw_free_blocks=self.cache.allocator.num_free,
                          retained_blocks=self.cache.retained_blocks)

    def can_admit(self, total_len: int) -> bool:
        """Whether the pool can host a sequence that may grow to
        ``total_len`` tokens (prompt + generation budget). Admission
        reserves the worst case up front so a running request can never
        strand mid-decode without a block (DESIGN_DECISIONS PR-9).
        Boolean view of :meth:`admit_probe` (slot availability excluded —
        the historical contract; the scheduler tracks slots itself)."""
        return self.admit_probe(total_len, include_slots=False).ok

    # -- request lifecycle -------------------------------------------------

    def stage_prompt(self, prompt: List[int]) -> np.ndarray:
        """Pad a prompt to the fixed prefill width — pure host work the
        scheduler runs at SUBMIT time (the PR-3 staging move:
        admission-path host prep happens off the tick's critical
        path). Chunked engines stage per-chunk at prefill time (the
        arrays are C-sized — already cheap)."""
        P = len(prompt)
        if not 0 < P <= self._W:
            raise ValueError(f"prompt length {P} not in [1, {self._W}]")
        if self.prefill_chunk is not None:
            return np.asarray([prompt], np.int32)    # chunked: raw ids
        ids = np.zeros((1, self._W), np.int32)
        ids[0, :P] = prompt
        return ids

    def _reserve(self, slot: int, prompt: List[int],
                 reserve_len: Optional[int]) -> Dict[str, int]:
        """Shared admission prologue: prefix-cache adopt + worst-case
        block reservation. Returns the slot's sharing stats."""
        P = len(prompt)
        target = max(P, reserve_len or P)
        match = self.cache.match_prefix(prompt)
        shared_len, hit_blocks = 0, 0
        if match is not None and match.blocks:
            self.cache.adopt_prefix(slot, match)
            shared_len, hit_blocks = match.length, match.hit_blocks
        if not self.cache.ensure_capacity(slot, target):
            self.cache.free_slot(slot)     # roll back the adoption
            raise RuntimeError(
                f"KV pool exhausted admitting slot {slot} "
                f"(need {self.cache.blocks_needed(target)} blocks, "
                f"{self.cache.free_blocks} free) — gate admissions on "
                f"can_admit()")
        stats = {"prefix_hit_blocks": hit_blocks,
                 "shared_len": shared_len,
                 "blocks_reserved": self.cache.owned_count(slot),
                 "cow_forks": 0, "prefill_chunks": 0,
                 "draft_proposed": 0, "draft_accepted": 0}
        self.slot_stats[slot] = stats
        return stats

    def _prefill_key(self) -> jnp.ndarray:
        """Per-admission PRNG key for a sampled first token (greedy
        engines trace the same operand but never use it)."""
        seed = self.sampling.seed if self.sampling is not None else 0
        return jax.random.fold_in(jax.random.PRNGKey(seed),
                                  1 + self.prefill_chunks + self.ticks)

    def admit(self, slot: int, prompt: List[int],
              reserve_len: Optional[int] = None,
              staged: Optional[np.ndarray] = None) -> int:
        """Prefill ``prompt`` into ``slot`` and return the first
        token. ``reserve_len`` (default: prompt length) eagerly
        allocates blocks for the sequence's full growth target;
        ``staged`` is an already-padded :meth:`stage_prompt` array. On
        a chunked engine this drives :meth:`begin_prefill` /
        :meth:`prefill_step` to completion in one call — schedulers
        interleave the steps instead."""
        self.begin_prefill(slot, prompt, reserve_len=reserve_len,
                           staged=staged)
        while True:
            tok = self.prefill_step(slot)
            if tok is not None:
                return tok

    def begin_prefill(self, slot: int, prompt: List[int],
                      reserve_len: Optional[int] = None,
                      staged: Optional[np.ndarray] = None) -> None:
        """Reserve ``slot`` for ``prompt`` (prefix-cache adoption +
        worst-case block reservation) and queue its prefill work.
        :meth:`prefill_step` runs one compiled prefill call at a time —
        the whole prompt for a legacy engine, one C-token chunk for a
        chunked one — and returns the first token when done."""
        assert not self.active[slot], f"slot {slot} is occupied"
        assert slot not in self._prefilling, f"slot {slot} is prefilling"
        P = len(prompt)
        if not 0 < P <= self._W:
            raise ValueError(f"prompt length {P} not in [1, {self._W}]")
        with tspan(self.tracer, "begin_prefill", slot=slot,
                   prompt_len=P) as sp:
            stats = self._reserve(slot, prompt, reserve_len)
            shared = stats["shared_len"]
            # an exact-duplicate prompt shares every block; still
            # re-attend the final position (writes masked) for the
            # first-token logits
            cursor = min(shared, P - 1)
            self._prefilling[slot] = {
                "prompt": list(prompt), "cursor": cursor,
                "shared_len": shared, "staged": staged}
            if sp is not None:
                sp.set(prefix_hit_blocks=stats["prefix_hit_blocks"])

    def prefill_step(self, slot: int) -> Optional[int]:
        """Run ONE compiled prefill call for a :meth:`begin_prefill`'d
        slot. Returns the first generated token when the prompt is fully
        processed (the slot is then live for decode ticks), else None —
        call again, ideally with decode ticks in between (that
        interleaving is chunked prefill's whole point)."""
        st = self._prefilling[slot]
        prompt, P = st["prompt"], len(st["prompt"])
        stats = self.slot_stats[slot]
        tr = live(self.tracer)
        # the enqueue: staging the operands and the compiled call
        with tspan(tr, "prefill_dispatch", slot=slot) as sp:
            if self.prefill_chunk is None:
                ids = st["staged"] if st["staged"] is not None \
                    else self.stage_prompt(prompt)
                self.cache.pools, tok = self._prefill_fn(
                    self.variables, self.cache.pools,
                    jnp.asarray(ids), jnp.asarray([P], jnp.int32),
                    jnp.asarray([st["shared_len"]], jnp.int32),
                    jnp.asarray(self.cache.tables[slot:slot + 1]),
                    self._prefill_key())
                done = True
            else:
                C = self.prefill_chunk
                cur = st["cursor"]
                n = min(C, P - cur)
                ids = np.zeros((1, C), np.int32)
                ids[0, :n] = prompt[cur:cur + n]
                self.cache.pools, tok, *counters = self._prefill_fn(
                    self.variables, self.cache.pools,
                    jnp.asarray(ids), jnp.asarray([cur], jnp.int32),
                    jnp.asarray([n], jnp.int32),
                    jnp.asarray([st["shared_len"]], jnp.int32),
                    self.cache.slot_tables(slot), self._prefill_key())
                st.setdefault("counters", []).extend(counters)
                st["cursor"] = cur + n
                done = st["cursor"] >= P
            stats["prefill_chunks"] += 1
            self.prefill_chunks += 1
            if sp is not None:
                sp.set(done=done)
        self._fed("prefill", sp, tr)
        if not done:
            return None
        # the drain: the wait for the device, the fetch of the token, then
        # the slot goes live and its prefix is registered
        with tspan(tr, "prefill_drain", slot=slot) as sp:
            del self._prefilling[slot]
            self.cache.lengths[slot] = P
            self.active[slot] = True
            counters = st.get("counters", ())
            jax.block_until_ready((tok, counters))
            with tspan(tr, "prefill_fetch") as fetch:
                self._starve("prefill", fetch)
                tok = int(tok)
                # every chunk's counters, fetched with the token: a
                # chunk's dispatch does not wait for the device, so the
                # prompt's counts are known here
                facts = self._count_experts(*counters)
            if sp is not None and facts:
                sp.set(**facts)
            with tspan(tr, "prefill_retire"):
                self.tokens[slot] = tok
                self.history[slot] = []
                self._bigram_idx[slot] = {}
                self._unigram_idx[slot] = {}
                self._history_append(slot, list(prompt) + [tok])
                self.cache.register_prefix(slot, prompt)
        return tok

    def _starve(self, after: str, span) -> None:
        """A drain's wait has returned: nothing is in flight from here
        until the next compiled call returns. The stretch starts where
        ``span`` (the drain's fetch, or None) starts."""
        self._starved_since = (
            after, time.perf_counter_ns() if span is None else span.t0_ns,
            None if span is None else span.t0_us)

    def _fed(self, by: str, span, tr) -> None:
        """A compiled call has returned (``span``, its dispatch, has
        ended, or is None): the chip has work again. Closes the open
        stretch, if any, into ``starved_s`` (host seconds) and, where
        ``tr`` is live, a retroactive ``starved`` span on ``tr``'s time
        base."""
        if self._starved_since is None:
            return
        (after, t0, t0_us), self._starved_since = self._starved_since, None
        t1 = time.perf_counter_ns() if span is None else span.t1_ns
        self.starved_s += (t1 - t0) / 1e9
        if tr is not None:
            tr.complete("starved",
                        tr.at_us(t0 / 1e9) if t0_us is None else t0_us,
                        tr.now_us() if span is None else span.t1_us,
                        after=after, by=by)

    def _count_experts(self, *counters) -> Dict[str, int]:
        """Fetch the counters of one or more calls in one go, add the
        ``expert_tokens`` (``[expert layers, experts held]`` int32 each) to
        the cumulative ``expert_pairs`` and ``expert_hits`` and return the
        calls' facts for a span: the pairs, the held experts that got a
        token, and the busiest expert's tokens; every other counter the
        model declares under its own name, summed over layers and calls.
        Nothing for a model that counts none."""
        counters = jax.device_get([c for c in counters if c])
        if not counters:
            return {}
        facts: Dict[str, int] = {}
        for name in counters[0]:
            values = [c[name] for c in counters]
            if name == "expert_tokens":
                facts.update(
                    expert_pairs=int(sum(c.sum() for c in values)),
                    expert_hits=int(sum((c > 0).sum() for c in values)),
                    expert_max=int(max(c.max() for c in values)))
            else:
                facts[name] = int(sum(c.sum() for c in values))
        self.expert_pairs += facts.get("expert_pairs", 0)
        self.expert_hits += facts.get("expert_hits", 0)
        return facts

    def evict(self, slot: int) -> None:
        """Free ``slot``'s blocks back to the pool (shared blocks
        survive until their LAST owner lets go); the lane masks off at
        the next tick. Stale pool contents are not wiped (finite,
        always length-masked) — reuse is a table edit."""
        self.cache.free_slot(slot)
        self.active[slot] = False
        self.tokens[slot] = 0
        self.history[slot] = []
        self._bigram_idx[slot] = {}
        self._unigram_idx[slot] = {}
        self._prefilling.pop(slot, None)

    # -- prefill/decode disaggregation (ISSUE 18) --------------------------

    def export_slot(self, slot: int):
        """Package a live slot's state for a prefill→decode handoff:
        ``(meta, kpages, vpages)`` where meta carries the KV length and
        block count and the pages are host numpy in table order (see
        :meth:`PagedKVCache.export_pages`). Exported at the moment the
        first token exists but no decode tick has run, the pages cover
        exactly the prompt — the pending first token's KV is written by
        the ADOPTING replica's first tick, so nothing transient is
        lost in flight."""
        assert self.active[slot], f"slot {slot} is not live"
        self.cache._kv_only("export_slot")
        P = int(self.cache.lengths[slot])
        ids, kpages, vpages = self.cache.export_pages(slot)
        meta = {"length": P, "blocks": len(ids),
                "quant": self.cache.quant_dtype}
        return meta, kpages, vpages

    def adopt_slot(self, slot: int, prompt: List[int], first_token: int,
                   kpages, vpages,
                   reserve_len: Optional[int] = None) -> bool:
        """Adopt a handed-off sequence into a free slot: import the
        streamed pages at this pool's own block ids, then rebuild the
        host lane state exactly as :meth:`prefill_step`'s completion
        would have — pending token, history (prompt + first token),
        drafter indices, prefix registration — so the first decode tick
        here is bit-identical to the tick a colocated replica would
        have run. Returns False on pool backpressure (nothing
        changed)."""
        assert not self.active[slot], f"slot {slot} is occupied"
        assert slot not in self._prefilling, f"slot {slot} is prefilling"
        self.cache._kv_only("adopt_slot")
        P = len(prompt)
        if not 0 < P <= self._W:
            raise ValueError(f"prompt length {P} not in [1, {self._W}]")
        # the import writes the device's pools: like a chunk's dispatch
        # it ends a starved stretch and opens none
        self._fed("import", None, live(self.tracer))
        if not self.cache.import_pages(slot, kpages, vpages, P,
                                       reserve_len=reserve_len):
            return False
        tok = int(first_token)
        self.active[slot] = True
        self.tokens[slot] = tok
        self.history[slot] = []
        self._bigram_idx[slot] = {}
        self._unigram_idx[slot] = {}
        self._history_append(slot, list(prompt) + [tok])
        self.cache.register_prefix(slot, prompt)
        self.slot_stats[slot] = {
            "prefix_hit_blocks": 0, "shared_len": 0,
            "blocks_reserved": self.cache.owned_count(slot),
            "cow_forks": 0, "prefill_chunks": 0,
            "draft_proposed": 0, "draft_accepted": 0}
        return True

    # -- speculation -------------------------------------------------------

    def _history_append(self, slot: int, toks: List[int]) -> None:
        """Append accepted tokens to the slot's history and keep the
        drafter's bigram/unigram occurrence maps current (each key holds
        the latest and previous-latest index — exactly what "most
        recent EARLIER occurrence of the tail" needs)."""
        h = self.history[slot]
        big, uni = self._bigram_idx[slot], self._unigram_idx[slot]
        for t in toks:
            h.append(t)
            j = len(h) - 1
            if j >= 1:
                key = (h[j - 1], t)
                big[key] = (j - 1, big.get(key, (None,))[0])
            uni[t] = (j, uni.get(t, (None,))[0])

    def _propose_drafts(self, slot: int) -> List[int]:
        """N-gram self-drafting (prompt-lookup decoding): find the most
        recent earlier occurrence of the history's tail bigram (then
        unigram) and propose its continuation; pad with the last
        proposed/known token (greedy tiny-model generations converge to
        short cycles, which is exactly what this predicts). Wrong drafts
        cost nothing but masked verify lanes — acceptance never drops
        below the non-speculative one token per tick. O(k) per call:
        the occurrence maps are maintained on append."""
        k = self.speculative
        h = self.history[slot]
        cont: List[int] = []
        if len(h) >= 2:
            cur, *prev = self._bigram_idx[slot].get((h[-2], h[-1]),
                                                    (None, None))
            i = prev[0] if cur == len(h) - 2 else cur
            if i is not None:
                cont = h[i + 2:i + 2 + k]
        if not cont and h:
            cur, *prev = self._unigram_idx[slot].get(h[-1], (None, None))
            i = prev[0] if cur == len(h) - 1 else cur
            if i is not None:
                cont = h[i + 1:i + 1 + k]
        pad = cont[-1] if cont else h[-1]
        return (cont + [pad] * k)[:k]

    # -- the tick ----------------------------------------------------------

    def _pre_tick_guard(self) -> np.ndarray:
        """Host guard before every tick: each active slot must own the
        block(s) its writes land in (fail loud, never a silent
        null-block scatter), and any ADOPTED shared block in the write
        range forks first — the copy-on-write point. Returns the live
        token count per slot ``n [S]`` (1 + accepted-capacity-clamped
        drafts)."""
        n = np.zeros((self.max_slots,), np.int32)
        for slot in np.flatnonzero(self.active):
            p = int(self.cache.lengths[slot])
            need = self.cache.blocks_needed(p + 1)
            if need > len(self.cache._owned[slot]):
                raise RuntimeError(
                    f"slot {slot} decoding past its reservation (length "
                    f"{p} needs block {need}, owns "
                    f"{len(self.cache._owned[slot])}) — admit with a "
                    f"larger reserve_len or call cache.ensure_capacity")
            cap = len(self.cache._owned[slot]) * self.cache.block_size - p
            n[slot] = max(1, min(self._K1, cap))
            for idx in self.cache.cow_targets(slot, p, p + int(n[slot])
                                              - 1):
                src, dst = self.cache.fork_block(slot, idx)
                src_i = jnp.asarray(src, jnp.int32)
                dst_i = jnp.asarray(dst, jnp.int32)
                for name in self.pool_names:
                    self.cache.pools[name] = self._cow_fn(
                        self.cache.pools[name], src_i, dst_i)
                    self._fed("cow", None, live(self.tracer))
                self.slot_stats[slot]["cow_forks"] = \
                    self.slot_stats[slot].get("cow_forks", 0) + 1
        return n

    def decode_tick(self) -> np.ndarray:
        """One compiled decode step over every slot. Appends each active
        slot's pending token (plus, with ``speculative=k``, its drafted
        guesses) to its KV, verifies/samples, and returns the new token
        front ``[S]`` (inactive lanes 0). ``last_accepted`` maps each
        active slot to the list of tokens it retired this tick — one for
        the plain tick, up to ``k+1`` under speculation."""
        t0 = time.perf_counter()
        tr = live(self.tracer)
        stochastic = self.speculative > 0 and self.sampling is not None
        with tspan(tr, "engine_tick", tick=self.ticks + 1) as tick_sp:
            # stage: the host guard, the block tables and every operand
            # of the compiled call
            with tspan(tr, "tick_stage"):
                n = self._pre_tick_guard()
                tables, lengths = self.cache.device_tables()
                drafted_tick, accepted_tick = 0, 0
                if self.speculative == 0:
                    if self.sampling is None:
                        keys = self._zero_keys      # greedy: unused operand
                    else:
                        keys = self._tick_keys(self.ticks)
                    operands = (jnp.asarray(self.tokens),
                                jnp.asarray(self.active), keys)
                else:
                    toks = np.zeros((self.max_slots, self._K1), np.int32)
                    for slot in np.flatnonzero(self.active):
                        drafts = self._propose_drafts(slot)
                        toks[slot, 0] = self.tokens[slot]
                        toks[slot, 1:] = drafts
                        drafted_tick += int(n[slot]) - 1
                    operands = (jnp.asarray(toks), jnp.asarray(n),
                                jnp.asarray(self.active))
                    if stochastic:
                        operands += (self._tick_keys(self.ticks),)
            # the enqueue: returns once XLA has the program
            with tspan(tr, "tick_dispatch") as sp:
                out = self._tick_fn(self.variables, self.cache.pools,
                                    tables, lengths, *operands)
            self._fed("tick", sp, tr)
            self.cache.pools = out[0]
            # the dispatch is async: host bookkeeping that doesn't need
            # the sampled tokens runs UNDER the in-flight device call (the
            # PR-3 overlap move at tick scale) — the plain tick advances
            # every active slot by exactly one, so its length bump
            # overlaps; speculative lengths depend on acceptance and must
            # wait.
            n_active = int(self.active.sum())
            if tick_sp is not None:
                seen = self.cache.lengths[self.active] + 1
                tick_sp.set(active=n_active, live_tokens=int(seen.sum()), **{
                    # the keys a window group's layers read: its window of
                    # every slot's and no more
                    f"live_tokens_{g.name}": int(
                        np.minimum(seen, g.window).sum())
                    for g in self.cache.groups.values() if g.window})
            if self.speculative == 0:
                self.cache.lengths[self.active] += 1
            # the drain: the host waits for the device here, then fetches
            with tspan(tr, "tick_drain"):
                jax.block_until_ready(out[1:])
                with tspan(tr, "tick_fetch") as sp:
                    self._starve("tick", sp)
                    if stochastic:
                        acc_d, res_d, bon_d = (np.asarray(o)
                                               for o in out[1:4])
                    else:
                        nxt = np.asarray(out[1])     # [S, 1] or [S, 1+k]
                    expert_facts = self._count_experts(
                        out[-1] if self.counter_names else {})
            with tspan(tr, "tick_retire"):
                self.last_accepted = {}
                front = np.zeros((self.max_slots,), np.int32)
                tokens_tick = 0
                for slot in np.flatnonzero(self.active):
                    if self.speculative == 0:
                        accepted = [int(nxt[slot, 0])]
                    elif stochastic:
                        # [S3] walk: accept drafts while the per-row coin
                        # lands under p(draft); the stopping row's token
                        # is the residual resample, or the bonus sample
                        # from the last live row when every draft survived
                        rows = int(n[slot])
                        take = 0
                        while take < rows - 1 and bool(acc_d[slot, take]):
                            take += 1
                        accepted = [int(toks[slot, j + 1])
                                    for j in range(take)]
                        if take < rows - 1:
                            accepted.append(int(res_d[slot, take]))
                        else:
                            accepted.append(int(bon_d[slot, rows - 1]))
                        accepted_tick += take
                        self.cache.lengths[slot] += len(accepted)
                    else:
                        # accept the longest draft prefix the model
                        # reproduced, plus the model's own token after it
                        # — identical to the sequential greedy stream by
                        # induction
                        take = 1
                        while (take < int(n[slot])
                               and int(toks[slot, take])
                               == int(nxt[slot, take - 1])):
                            take += 1
                        accepted = [int(t) for t in nxt[slot, :take]]
                        accepted_tick += take - 1
                        self.cache.lengths[slot] += len(accepted)
                    self.last_accepted[slot] = accepted
                    front[slot] = accepted[-1]
                    self._history_append(slot, accepted)
                    tokens_tick += len(accepted)
                    st = self.slot_stats[slot]
                    st["draft_proposed"] = st.get("draft_proposed", 0) \
                        + (int(n[slot]) - 1 if self.speculative else 0)
                    st["draft_accepted"] = st.get("draft_accepted", 0) \
                        + len(accepted) - 1
                self.tokens = front
                self.ticks += 1
                self.tokens_generated += tokens_tick
                self.draft_proposed += drafted_tick
                self.draft_accepted += accepted_tick
            if tick_sp is not None:
                tick_sp.set(tokens=tokens_tick,
                            accepted_drafts=accepted_tick, **expert_facts)
        if self.telemetry is not None:
            wall = time.perf_counter() - t0
            # sharing/chunk counters are emitted as PER-TICK DELTAS
            # (admissions land between ticks, so their hits show up on
            # the next record): every decode_tick field aggregates the
            # same way — sum over records — with no cumulative mix-ins
            snap = {"prefix_hit_blocks": self.cache.prefix_hit_blocks,
                    "cow_forks": self.cache.cow_forks,
                    "prefill_chunks": self.prefill_chunks,
                    "retained_hits": self.cache.retained_hits}
            delta = {key: val - self._tick_counters.get(key, 0)
                     for key, val in snap.items()}
            self._tick_counters = snap
            self.telemetry.emit_event({
                "kind": "decode_tick", "tick": self.ticks,
                "active_slots": n_active, "wall_ms": round(wall * 1e3, 4),
                "tokens": tokens_tick,
                "tokens_per_sec": round(tokens_tick / wall, 2)
                if wall else None,
                "free_blocks": self.cache.free_blocks,
                "draft_accept_rate": round(accepted_tick / drafted_tick,
                                           4) if drafted_tick else None,
                # gauges, not per-tick deltas: the retained-LRU size and
                # the pool's capacity accounting (ISSUE 14); with a tp
                # mesh kv_bytes_per_token is PER SHARD and tp_degree
                # carries the mesh width (ISSUE 15)
                "retained_blocks": self.cache.retained_blocks,
                "kv_bytes_per_token": self.cache.kv_bytes_per_token,
                "quant_dtype": self.cache.quant_dtype,
                "tp_degree": self.tp_degree,
                **delta, **expert_facts,
            })
        if self.metrics is not None:
            m = self.metrics
            m.histogram("engine_tick_ms",
                        "compiled decode tick wall time (ms)").observe(
                (time.perf_counter() - t0) * 1e3)
            m.counter("engine_ticks", "decode ticks executed").inc()
            m.counter("engine_tokens",
                      "tokens retired across all slots").inc(tokens_tick)
            m.gauge("engine_active_slots",
                    "slots decoding this tick").set(n_active)
            # KV pool occupancy: reserved fraction of the paged pool
            m.gauge("engine_kv_free_blocks",
                    "free blocks in the paged KV pool").set(
                self.cache.free_blocks)
            m.gauge("engine_kv_occupancy",
                    "reserved fraction of the paged KV pool").set(
                1.0 - self.cache.free_blocks / self.cache.num_blocks)
            # sharing/speculation counters as per-tick increments, via
            # a snapshot diff SEPARATE from telemetry's (each consumer
            # owns its own baseline; sharing one would starve whichever
            # reads second)
            snap = {"engine_prefix_hit_blocks":
                    self.cache.prefix_hit_blocks,
                    "engine_cow_forks": self.cache.cow_forks,
                    "engine_prefill_chunks": self.prefill_chunks,
                    "engine_draft_proposed": self.draft_proposed,
                    "engine_draft_accepted": self.draft_accepted}
            for key, val in snap.items():
                d = val - self._metrics_tick_counters.get(key, 0)
                if d:
                    m.counter(key, "cumulative engine counter").inc(d)
            self._metrics_tick_counters = snap
        return self.tokens.copy()

    # -- observability -----------------------------------------------------

    def _tick_args(self):
        """The tick's operands at the engine's shapes (zeros where the
        host stages them per tick)."""
        tables, lengths = self.cache.device_tables()
        args = (self.variables, self.cache.pools, tables, lengths)
        active = jnp.asarray(self.active)
        keys = jnp.zeros((self.max_slots, 2), jnp.uint32)
        if self.speculative == 0:
            return args + (jnp.asarray(self.tokens), active, keys)
        args += (jnp.zeros((self.max_slots, self._K1), jnp.int32),
                 jnp.ones((self.max_slots,), jnp.int32), active)
        # the stochastic verify tick takes keys, the greedy one does not
        return args + (keys,) if self.sampling is not None else args

    def _prefill_args(self):
        """The prefill's operands at the engine's shapes: slot 0's table,
        one live token of zeros (``warmup()`` runs the program on them)."""
        table = self.cache.slot_tables(0)
        one, zero = jnp.asarray([1], jnp.int32), jnp.asarray([0], jnp.int32)
        args = (self.variables, self.cache.pools)
        if self.prefill_chunk is None:      # ids, length, start
            args += (jnp.zeros((1, self._W), jnp.int32), one, zero)
        else:                               # ids, start, n, write_from
            args += (jnp.zeros((1, self.prefill_chunk), jnp.int32),
                     zero, one, zero)
        return args + (table, self._prefill_key())

    def lower_tick(self):
        """The decode tick lowered at the engine's shapes, not run
        (``jax.stages.Lowered``): how a caller reads the tick's compiled text
        (``lower_tick().compile().as_text()``). The pools are untouched."""
        return self._tick_fn.lower(*self._tick_args())
