"""Decoder-only transformer LM — the flagship long-context showcase tying
the modern additions together: multi-head attention with the optional Pallas
flash path, pre-LN residual blocks, and optional mixture-of-experts FFNs.

The 2017 reference predates transformers entirely (SURVEY §5 records the
absence of any attention-era machinery) — this model family is a deliberate
"exceeds" item, built from the same Module/IR system as everything else, so
it exports, shards (ring/Ulysses for the seq axis, expert axis for MoE), and
trains under the standard Trainer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from jax.sharding import NamedSharding, PartitionSpec

from paddle_tpu.core import initializers as I
from paddle_tpu.core.dtypes import current_policy
from paddle_tpu.core.module import Module, is_initializing
from paddle_tpu.nn.attention import MultiHeadAttention
from paddle_tpu.nn.layers import Dropout, Embedding, LayerNorm, Linear
from paddle_tpu.nn.moe import MoEFFN

__all__ = ["TransformerBlock", "TransformerLM", "remat_policy"]

# the node of a prepared tree that holds the blocks' stack
# (``TransformerLM.serving_variables``); a training tree has ``block<i>``
_STACK = "blocks"


def remat_policy(name):
    """Map a remat knob value to a ``jax.checkpoint`` policy.

    - ``"dots"`` (or ``True``): save matmul outputs, rematerialize the
      cheap elementwise/norm tail (``dots_saveable`` — the standard
      transformer trade: activation memory drops to the dot products while
      the backward recompute stays a small fraction of step FLOPs).
    - ``"full"``: save nothing between layer boundaries — maximum memory
      saving, one extra full forward in the backward.
    """
    if name in (True, "dots"):
        return jax.checkpoint_policies.dots_saveable
    if name == "full":
        return None
    raise ValueError(f"remat must be None, 'dots', or 'full'; got {name!r}")


class TransformerBlock(Module):
    """Pre-LN block: ``x + MHA(LN(x))`` then ``x + FFN(LN(x))``; the FFN is
    a dense two-layer gelu MLP or an :class:`MoEFFN` when
    ``moe_experts > 0``."""

    # The leaves of a block's subtree that the block itself passes through
    # ``cast_compute`` as a product's operand: the four projections
    # (``nn/attention.py``) and the dense MLP's two matrices (``Linear``).
    # ``TransformerLM.serving_variables`` holds exactly these in the
    # policy's compute type; biases and LayerNorm leaves are never cast,
    # and ``MoEFFN`` multiplies its leaves (``ffn/wg``, ``w1``, ``w2``) as
    # they are stored.
    compute_operands = ("attn/wq", "attn/wk", "attn/wv", "attn/wo",
                        "ffn1/w", "ffn2/w")

    def __init__(self, dim: int, num_heads: int, ffn_hidden: int,
                 use_flash: bool = False, moe_experts: int = 0,
                 dropout: float = 0.0, attention_impl=None, seq_mesh=None,
                 seq_axis: str = "seq", batch_axis=None,
                 residual_sharding=None, name=None):
        super().__init__(name=name)
        # Optional ``x -> x`` callable (typically a with_sharding_constraint
        # closure) applied to the residual stream after each sublayer add.
        # Constraining residuals to a seq-sharded spec (e.g.
        # P("data", "model", None)) turns Megatron tensor-parallel's
        # activation all-reduces into reduce-scatter/all-gather pairs —
        # sequence-parallel residuals, halving tp wire bytes.
        self.residual_sharding = residual_sharding
        self.ln1 = LayerNorm()
        self.attn = MultiHeadAttention(num_heads, use_flash=use_flash,
                                       attention_impl=attention_impl,
                                       seq_mesh=seq_mesh, seq_axis=seq_axis,
                                       batch_axis=batch_axis)
        self.ln2 = LayerNorm()
        self.moe_experts = moe_experts
        if moe_experts > 0:
            self.ffn = MoEFFN(moe_experts, ffn_hidden)
        else:
            self.ffn1 = Linear(ffn_hidden, act="gelu")
            self.ffn2 = Linear(dim)
        self.dropout = Dropout(dropout) if dropout else None

    def forward(self, x, train: bool = False, segments=None,
                return_kv: bool = False):
        # named_scope: profiler traces (obs/trace.py:jax_profile) show
        # model structure instead of anonymous fusions — trace-time
        # metadata only, zero runtime effect.
        with jax.named_scope("attn"):
            a = self.attn(self.ln1(x), causal=True, segments=segments,
                          return_kv=return_kv)
            kv = None
            if return_kv:
                a, kv = a
            h = x + self._maybe_drop(a, train)
        if self.residual_sharding is not None:
            h = self.residual_sharding(h)
        with jax.named_scope("ffn"):
            z = self.ln2(h)
            if self.moe_experts > 0:
                y, aux = self.ffn(z, return_aux=True)
            else:
                y = self.ffn2(self.ffn1(z))
                aux = jnp.zeros((), jnp.float32)
            out = h + self._maybe_drop(y, train)
        if self.residual_sharding is not None:
            out = self.residual_sharding(out)
        if return_kv:
            return out, aux, kv
        return out, aux

    def decode_step(self, x, pages_k, pages_v, layer, tables, positions,
                    active, attn_impl: str = "xla"):
        """One serving decode step: the forward block with the attention
        sublayer swapped for :meth:`MultiHeadAttention.decode` (paged KV
        write + q_len=1 attention). ``pages_k``/``pages_v`` are every
        layer's pools and ``layer`` this block's number; returns
        ``(out, pages_k, pages_v)`` with the pools whole, this layer's
        rows written. No dropout — serving is inference-only by
        construction."""
        with jax.named_scope("attn"):
            a, pages_k, pages_v = self.attn.decode(
                self.ln1(x), pages_k, pages_v, layer, tables, positions,
                active, impl=attn_impl)
            h = x + a
        if self.residual_sharding is not None:
            h = self.residual_sharding(h)
        with jax.named_scope("ffn"):
            z = self.ln2(h)
            if self.moe_experts > 0:
                y, _aux = self.ffn(z, return_aux=True)
            else:
                y = self.ffn2(self.ffn1(z))
            out = h + y
        if self.residual_sharding is not None:
            out = self.residual_sharding(out)
        return out, pages_k, pages_v

    def decode_span(self, x, pages_k, pages_v, layer, tables, start, n,
                    active, attn_impl: str = "xla", write_from=None):
        """A span of consecutive new tokens per slot: the forward block
        with the attention sublayer swapped for
        :meth:`MultiHeadAttention.decode_span` (multi-token paged
        write + per-row q_len=1-exact attention). Shared by the
        speculative verify tick and chunked prefill (ISSUE 12).
        ``x`` [S, Q, D]; pools and ``layer`` as in :meth:`decode_step`;
        returns ``(out, pages_k, pages_v)``."""
        with jax.named_scope("attn"):
            a, pages_k, pages_v = self.attn.decode_span(
                self.ln1(x), pages_k, pages_v, layer, tables, start, n,
                active, impl=attn_impl, write_from=write_from)
            h = x + a
        if self.residual_sharding is not None:
            h = self.residual_sharding(h)
        with jax.named_scope("ffn"):
            z = self.ln2(h)
            if self.moe_experts > 0:
                y, _aux = self.ffn(z, return_aux=True)
            else:
                y = self.ffn2(self.ffn1(z))
            out = h + y
        if self.residual_sharding is not None:
            out = self.residual_sharding(out)
        return out, pages_k, pages_v

    def _maybe_drop(self, x, train):
        if self.dropout is not None and train:
            return self.dropout(x, train=True)
        return x


class TransformerLM(Module):
    """``ids [B, T] -> logits [B, T, vocab]`` with tied input/output
    embeddings. ``forward(ids, train, return_aux=True)`` also returns the
    summed MoE load-balance loss (zero for dense FFNs)."""

    def __init__(self, vocab: int, dim: int = 128, num_layers: int = 2,
                 num_heads: int = 4, ffn_hidden: int = 256,
                 max_len: int = 512, use_flash: bool = False,
                 moe_experts: int = 0, dropout: float = 0.0,
                 attention_impl=None, seq_mesh=None, seq_axis: str = "seq",
                 batch_axis=None, residual_sharding=None, remat=None,
                 name="transformer_lm"):
        super().__init__(name=name)
        self.max_len = max_len
        self.residual_sharding = residual_sharding
        # remat: None (off), "dots"/True, or "full" — runs the block stack
        # as ONE lax.scan over stacked per-layer params with jax.checkpoint
        # around the body: layer-boundary activations are the only thing
        # saved across the stack (policy-dependent within a layer), turning
        # activation memory from O(L * T * D * blowup) into
        # O(L boundaries + one layer's working set) — the standard
        # scan-over-layers + rematerialization recipe. Requires homogeneous
        # blocks and dropout == 0; the variables tree is UNCHANGED
        # (per-block subtrees are stacked at trace time), so checkpoints
        # move freely between remat and plain configs.
        if remat is not None:
            remat_policy(remat)          # validate eagerly
        self.remat = remat
        self.dropout_rate = dropout
        self.emb = Embedding(vocab, dim)
        self.pos = Embedding(max_len, dim,
                             w_init=I.normal(0.02), name="pos")
        self.blocks = [TransformerBlock(dim, num_heads, ffn_hidden,
                                        use_flash, moe_experts, dropout,
                                        attention_impl=attention_impl,
                                        seq_mesh=seq_mesh, seq_axis=seq_axis,
                                        batch_axis=batch_axis,
                                        residual_sharding=residual_sharding,
                                        name=f"block{i}")
                       for i in range(num_layers)]
        self.ln_f = LayerNorm()

    def cache_spec(self):
        """What the serving engine asks a model (``serve/engine.py``):
        ``layers``, and ``pools``: the paged state a token leaves in one
        layer, as named rows. Multi-head attention keeps a ``k`` and a
        ``v`` row of ``[heads, head size]``; the entry points take and
        return them in this order, ``kv = (k, v, tables)``."""
        attn = self.blocks[0].attn
        row = (attn.num_heads, attn.head_dim or self.emb.dim // attn.num_heads)
        return {"layers": len(self.blocks), "pools": {"k": row, "v": row}}

    def embed(self, ids, positions=None):
        """Token + positional embedding only (the pipeline-parallel entry:
        stage 0's input is produced outside the block pipeline)."""
        T = ids.shape[1]
        pos = jnp.arange(T)[None] if positions is None else positions
        return self.emb(ids) + self.pos(pos)

    def head(self, x):
        """Final LN + tied readout (the pipeline-parallel exit)."""
        return self.emb.attend(self.ln_f(x))

    def forward(self, ids, train: bool = False, return_aux: bool = False,
                segments=None, positions=None):
        """``segments``/``positions``: packed-sequence metadata
        (``core.sequence.pack_sequences``) — attention is confined within
        each packed sub-sequence on every attention impl, and positional
        embeddings restart per segment when ``positions`` is given."""
        T = ids.shape[1]
        assert T <= self.max_len, f"T={T} exceeds max_len={self.max_len}"
        pos = jnp.arange(T)[None] if positions is None else positions
        with jax.named_scope("embed"):
            x = self.emb(ids) + self.pos(pos)
        if self.residual_sharding is not None:
            x = self.residual_sharding(x)
        if self.remat is not None and not is_initializing():
            # init must trace the plain loop so every block creates its
            # params; apply takes the scanned/rematerialized stack.
            x, aux_total = self._scan_blocks(x, train, segments)
        else:
            aux_total = jnp.zeros((), jnp.float32)
            for blk in self.blocks:
                with jax.named_scope(blk._name):
                    x, aux = blk(x, train=train, segments=segments)
                aux_total = aux_total + aux
        with jax.named_scope("head"):
            x = self.ln_f(x)
            logits = self.emb.attend(x)      # tied softmax weights
        if return_aux:
            return logits, aux_total
        return logits

    # -- serving entry points (paddle_tpu.serve) ---------------------------
    #
    # All three run the block stack as ONE lax.scan over the per-block
    # param subtrees stacked on a leading layer axis (the _scan_blocks
    # recipe, minus checkpoint — no gradients flow here). The stack is
    # a function of the weights alone, so whoever calls them every tick
    # makes it ONCE: `serving_variables` turns the training tree into the
    # tree whose `blocks` node IS the stack (the products' operands already
    # in the policy's compute type), and the engine holds that. Handed the
    # training tree itself (a checkpoint, `InferenceModel.decode_step`, a
    # test) the entry points stack at trace time, inside the program, every
    # call: `_stacked_blocks` tells the two apart by the tree's structure.
    #
    # Shard-in-scope (ISSUE 15): the bodies are mesh-oblivious, but when
    # the engine traces them inside `parallel.tp_shard_scope` the
    # attention layer pins its projections/pools head-sharded and the
    # residual stream + logits pin REPLICATED here — classic Megatron tp
    # (not sequence-parallel: decode is one token per slot, so there is
    # no sequence to split; the head axis is the only parallel axis with
    # work on it). The logits assemble on the existing tp head path: the
    # row-parallel out/ffn2 projections all-reduce back to the replicated
    # residual, and the tied readout runs replicated on every shard.

    def _stack(self, subs):
        """The blocks' subtrees -> one subtree on a leading ``[L, ...]``
        layer axis, :attr:`TransformerBlock.compute_operands` in the
        CURRENT policy's compute type (the cast the block would make in
        every product: bfloat16 of a float32 value is one value whenever
        it is computed; under the float32 policy it is the identity)."""
        dtype = current_policy().compute_dtype
        operands = self.blocks[0].compute_operands

        def stack(path, *leaves):
            out = jnp.stack(leaves)
            name = "/".join(str(k.key) for k in path)
            return out.astype(dtype) if name in operands else out

        return jax.tree_util.tree_map_with_path(stack, *subs)

    def serving_variables(self, variables):
        """What the serving engine asks a model beside ``cache_spec()``:
        the variables tree :meth:`prefill`, :meth:`decode_step` and
        :meth:`decode_span` run on, made ONCE from the training tree. The
        ``block<i>`` subtrees become one ``blocks`` subtree
        (:meth:`_stack`); both embeddings, the final LayerNorm and every
        other collection come back as the objects they were (the tied
        readout casts nothing). The result is right for the policy it was
        made under and for no other.

        One jitted program over the blocks' leaves alone (a jit copies
        what it passes through). Leaves that a mesh holds keep their
        layout behind an unsharded layer axis, pinned on the program's
        outputs: ``P(None, *spec)``."""
        root = variables["params"]
        name = self._name if self._name in root else next(iter(root))
        own = dict(root[name])
        subs = [own.pop(blk._name) for blk in self.blocks]

        def behind_layer_axis(leaf):
            at = getattr(leaf, "sharding", None)
            return (NamedSharding(at.mesh, PartitionSpec(None, *at.spec))
                    if isinstance(at, NamedSharding) else None)

        # a new function every call: jit keys its cache on the function
        # and the arguments, and the policy is neither
        own[_STACK] = jax.jit(
            lambda subs: self._stack(subs),
            out_shardings=jax.tree_util.tree_map(behind_layer_axis,
                                                 subs[0]))(subs)
        return {**variables, "params": {**root, name: own}}

    def _stacked_blocks(self):
        """``(block0, stack)`` for the layer scan: the stack the tree
        holds (:meth:`serving_variables`), else the training tree's
        blocks stacked here, at trace time."""
        own = self.subtree()
        if _STACK in own:
            return self.blocks[0], own[_STACK]
        return self.blocks[0], self._stack(
            [blk.subtree() for blk in self.blocks])

    def prefill(self, ids, positions=None):
        """Serving prefill: ``ids [B, W] -> (logits [B, W, vocab],
        (k, v))`` where ``k``/``v`` are the per-layer attention
        projections ``[L, B, W, H, hd]`` — the engine scatters rows
        ``< length`` into the paged KV cache. ``W`` is the engine's FIXED
        padded context width: rows past a sequence's true length produce
        unspecified logits/KV (causal masking keeps them out of every
        valid row), and running every prefill at one width both pins the
        compiled shape (no retraces) and keeps each row's softmax
        reduction width identical to the training forward's — the f32
        bit-equality contract the serve tests pin."""
        from paddle_tpu.parallel.sharding import tp_constrain
        T = ids.shape[1]
        assert T <= self.max_len, f"T={T} exceeds max_len={self.max_len}"
        pos = jnp.arange(T)[None] if positions is None else positions
        with jax.named_scope("decode/prefill"):
            with jax.named_scope("embed"):
                x = tp_constrain(self.emb(ids) + self.pos(pos))
            block0, stacked = self._stacked_blocks()

            def body(h, bp):
                y, _aux, kv = block0.apply(
                    {"params": {block0._name: bp}}, h, train=False,
                    return_kv=True)
                return tp_constrain(y), kv

            with jax.named_scope("block_scan"):
                x, (ks, vs) = lax.scan(body, x, stacked)
            with jax.named_scope("head"):
                logits = tp_constrain(self.emb.attend(self.ln_f(x)))
        return logits, (ks, vs)

    def decode_step(self, token, kv, positions, active=None,
                    attn_impl: str = "xla"):
        """Serving decode tick: one new token per slot against the paged
        KV cache. ``token [S]`` int32; ``kv = (pages_k, pages_v,
        tables)`` with pools ``[L, N, H, bs, hd]`` (the layer scan
        CARRIES them whole beside the residual stream: each layer writes
        its rows in place and reads its pages by layer index, so no
        pool-sized array is ever sliced out, copied or collected) and
        ``tables [S, MB]``; ``positions
        [S]`` the incoming token's 0-based position (== pre-step length);
        ``active [S]`` bool (default: all). Returns ``(logits [S,
        vocab], kv')`` with the updated pools — same structure, so the
        engine's jit carry donates cleanly."""
        from paddle_tpu.parallel.sharding import tp_constrain
        pages_k, pages_v, tables = kv
        S = token.shape[0]
        if active is None:
            active = jnp.ones((S,), bool)
        # inactive slots may carry position 0 forever; the clamp only
        # guards overflow and is the identity for every valid position
        pos_idx = jnp.minimum(positions, self.max_len - 1)
        with jax.named_scope("decode/step"):
            with jax.named_scope("embed"):
                x = tp_constrain(self.emb(token[:, None])
                                 + self.pos(pos_idx[:, None]))
            block0, stacked = self._stacked_blocks()

            def body(carry, xs):
                h, pk, pv = carry
                bp, layer = xs
                y, pk, pv = block0.apply(
                    {"params": {block0._name: bp}}, h, pk, pv, layer,
                    tables, positions, active, attn_impl=attn_impl,
                    method="decode_step")
                return (tp_constrain(y), pk, pv), None

            with jax.named_scope("block_scan"):
                (x, pages_k, pages_v), _ = lax.scan(
                    body, (x, pages_k, pages_v),
                    (stacked, jnp.arange(len(self.blocks))))
            with jax.named_scope("head"):
                logits = tp_constrain(self.emb.attend(self.ln_f(x)))
        return logits[:, 0], (pages_k, pages_v, tables)

    def decode_span(self, tokens, kv, start, n, active=None,
                    attn_impl: str = "xla", write_from=None):
        """Serving span step: ``Q`` consecutive new tokens per slot
        against the paged KV cache — ONE compiled dispatch that the
        speculative verify tick (``Q = 1 + draft_k``) and chunked
        prefill (``Q = chunk``) both ride (ISSUE 12). ``tokens``
        ``[S, Q]`` int32 (token ``j`` of slot ``s`` at position
        ``start[s] + j``); ``n`` ``[S]`` live token counts (rows past
        ``n`` are padding — null-block scatter, garbage logits);
        ``write_from`` ``[S]`` optional scatter floor for shared-prefix
        re-reads. Returns ``(logits [S, Q, vocab], kv')``; row ``j`` of
        a live slot is bit-equal (f32) to what :meth:`decode_step`
        would produce at that position — the structural losslessness
        the serve tests pin."""
        from paddle_tpu.parallel.sharding import tp_constrain
        pages_k, pages_v, tables = kv
        S, Q = tokens.shape
        if active is None:
            active = jnp.ones((S,), bool)
        pos = jnp.minimum(start[:, None]
                          + jnp.arange(Q, dtype=jnp.int32)[None, :],
                          self.max_len - 1)
        with jax.named_scope("decode/span"):
            with jax.named_scope("embed"):
                x = tp_constrain(self.emb(tokens) + self.pos(pos))
            block0, stacked = self._stacked_blocks()

            def body(carry, xs):
                h, pk, pv = carry
                bp, layer = xs
                y, pk, pv = block0.apply(
                    {"params": {block0._name: bp}}, h, pk, pv, layer,
                    tables, start, n, active, attn_impl=attn_impl,
                    write_from=write_from, method="decode_span")
                return (tp_constrain(y), pk, pv), None

            with jax.named_scope("block_scan"):
                (x, pages_k, pages_v), _ = lax.scan(
                    body, (x, pages_k, pages_v),
                    (stacked, jnp.arange(len(self.blocks))))
            with jax.named_scope("head"):
                logits = tp_constrain(self.emb.attend(self.ln_f(x)))
        return logits, (pages_k, pages_v, tables)

    def grad_sync_scan_paths(self):
        """The ``parallel.overlap`` in-scan protocol: fnmatch patterns (over
        slash-joined param paths) of the leaves this model gradient-syncs
        PER LAYER inside its scan-over-layers stack — the Trainer's
        bucketed grad_sync excludes them from its top-level buckets so
        they are never double-synced. Only the remat'd stack scans, so
        without ``remat`` there is nothing to claim."""
        if self.remat is None:
            return ()
        return ("*/block*/*",)

    def _scan_blocks(self, x, train, segments):
        """The rematerialized stack: stack the (homogeneous) per-block param
        subtrees onto a leading [L, ...] layer axis and run ONE
        ``jax.checkpoint``-wrapped block as a ``lax.scan`` over it. Grads
        flow back through the stack's transpose (unstack) onto the
        per-block leaves, so the optimizer/checkpoint view of the params is
        unchanged."""
        assert not (train and self.dropout_rate > 0), \
            "remat scan-over-layers requires dropout == 0 (rngs do not " \
            "thread through the stacked block)"
        block0 = self.blocks[0]
        subs = [blk.subtree() for blk in self.blocks]
        stacked = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *subs)

        def body(carry, bp):
            h, aux = carry
            # Per-layer in-scan gradient sync (no-op outside an active
            # Trainer grad_sync="bucketed" trace): the stacked leaves'
            # gradient only completes when the WHOLE scan transpose
            # finishes, so the bucket marker wraps each layer's param
            # slice HERE — its all-reduce fires inside that layer's
            # backward iteration. Lazy import: parallel imports models.
            from paddle_tpu.parallel import overlap as _overlap
            bp = _overlap.sync_scan_slice(bp, tag="scan_layer")
            with jax.named_scope("block_scan"):
                y, a = block0.apply({"params": {block0._name: bp}}, h,
                                    train=train, segments=segments)
            return (y, aux + a), None

        body = jax.checkpoint(body, policy=remat_policy(self.remat))
        (x, aux_total), _ = lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), stacked)
        return x, aux_total


def make_pipeline_lm_apply(model: "TransformerLM", mesh, microbatches: int,
                           pipe_axis: str = "pipe"):
    """Pipeline-parallel forward for a :class:`TransformerLM`: the block
    stack executes as a GPipe wavefront over the mesh's ``pipe`` axis
    (one block per stage), embeddings/head stay outside the pipeline —
    making pipeline parallelism reachable from the model library rather
    than only from hand-built toys (the integration gap VERDICT r2 called
    out for the sequence-parallel wrappers).

    Returns ``apply_fn(variables, ids, positions=None) -> logits`` that is
    numerically identical to ``model.apply`` (the wavefront is
    differentiable, so ``jax.grad`` through ``apply_fn`` trains embeddings,
    blocks, and head end to end). Requires ``len(model.blocks)`` == the
    ``pipe`` axis size, homogeneous blocks, and ``dropout == 0`` (rngs
    don't cross the shard_map boundary). For the M >> S
    gradient-accumulation regime use
    :func:`paddle_tpu.parallel.make_pipeline_1f1b` directly.
    """
    import jax

    from ..parallel.pipeline import make_pipeline

    S = len(model.blocks)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    assert sizes.get(pipe_axis) == S, \
        f"pipe axis size {sizes.get(pipe_axis)} != num_layers {S}"
    block0 = model.blocks[0]

    def stage_fn(p_stage, act):
        out, _aux = block0.apply({"params": p_stage}, act)
        return out

    pipe = make_pipeline(mesh, stage_fn, pipe_axis)

    def stack_blocks(variables):
        root = variables["params"]
        mp = root[model._name] if model._name in root \
            else next(iter(root.values()))
        subs = [mp[blk._name] for blk in model.blocks]
        stacked = jax.tree_util.tree_map(
            lambda *ls: jnp.stack(ls), *subs)
        return {block0._name: stacked}

    def apply_fn(variables, ids, positions=None):
        h = model.apply(variables, ids, positions=positions, method="embed")
        B = h.shape[0]
        assert B % microbatches == 0, \
            f"batch {B} must divide by microbatches {microbatches}"
        x_mb = h.reshape(microbatches, B // microbatches, *h.shape[1:])
        out = pipe(stack_blocks(variables), x_mb)
        out = out.reshape(B, *h.shape[1:])
        return model.apply(variables, out, method="head")

    return apply_fn


__all__ += ["make_pipeline_lm_apply"]
