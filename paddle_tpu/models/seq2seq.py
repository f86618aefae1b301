"""Attention seq2seq NMT — the reference's flagship recurrent workload.

Reference: encoder-decoder with ``simple_attention`` inside a recurrent group
(``/root/reference/python/paddle/trainer_config_helpers/networks.py:1320``;
demo ``v1_api_demo/seqToseq`` equivalent; the decoder unroll + beam-search
generation is ``RecurrentGradientMachine::generateSequence`` /
``beamSearch``, ``paddle/gserver/gradientmachines/RecurrentGradientMachine.cpp:539``).

TPU-native: the encoder is a BiRNN scan; the decoder trains teacher-forced under
one scan (no per-step Python); generation is a fixed-width beam search inside
``lax.scan`` over decode steps — fully jittable, static shapes, runs on-device
(the reference's dynamic ``Path`` expansion becomes tensor-shaped beam state).

Token conventions: 0 = pad, 1 = <s> (bos), 2 = <e> (eos), matching the
reference's seqToseq data convention.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..core.module import Module
from ..core.sequence import length_mask
from .. import nn

__all__ = ["Seq2SeqAttention", "PAD", "BOS", "EOS"]

PAD, BOS, EOS = 0, 1, 2


class Seq2SeqAttention(Module):
    """GRU encoder-decoder with additive attention.

    forward(batch) -> per-example loss (teacher forcing).
    ``generate`` -> beam-search decode (jittable).
    """

    def __init__(self, src_vocab: int, tgt_vocab: int, emb_dim: int = 128,
                 hidden: int = 256, name=None):
        super().__init__(name=name)
        self.src_vocab = src_vocab
        self.tgt_vocab = tgt_vocab
        self.hidden = hidden
        self.src_emb = nn.Embedding(src_vocab, emb_dim, name="src_emb")
        self.tgt_emb = nn.Embedding(tgt_vocab, emb_dim, name="tgt_emb")
        self.encoder = nn.BiRNN(nn.GRUCell(hidden), nn.GRUCell(hidden),
                                name="encoder")
        self.dec_cell = nn.GRUCell(hidden, name="dec_cell")
        self.att = nn.AdditiveAttention(hidden, name="att")
        self.boot = nn.Linear(hidden, act="tanh", name="boot")
        self.readout = nn.Linear(tgt_vocab, name="readout")

    # -- shared pieces --------------------------------------------------------

    def encode(self, src_ids, src_len):
        mask = length_mask(src_len, src_ids.shape[1])
        enc = self.encoder(self.src_emb(src_ids), mask=mask)   # [B, T, 2H]
        # boot state from the backward encoder's first output (the reference
        # boots the decoder from backward_first, networks.py simple_attention
        # usage in seqToseq)
        back_first = enc[:, 0, self.hidden:]
        dec0 = self.boot(back_first)
        return enc, mask, dec0

    def _dec_cell_step(self, state, y_emb, enc, enc_mask, enc_proj):
        """One decoder step WITHOUT the vocab readout — the readout is 83%
        of decoder FLOPs (2*h*V per token) and, run per scan step as a tiny
        [B, h] @ [h, V] matmul, dominated the step at single-digit MXU
        efficiency (PERF.md (older installation) "Round 5: seq2seq"); training
        hoists it out of the scan and applies it once over [B, T, h]."""
        ctx, _ = self.att(state, enc, enc_mask, enc_proj=enc_proj)
        x = jnp.concatenate([y_emb, ctx], axis=-1)
        new_state, out = self.dec_cell.step(state, x)
        return new_state, out

    def _dec_step(self, state, y_emb, enc, enc_mask, enc_proj):
        new_state, out = self._dec_cell_step(state, y_emb, enc, enc_mask,
                                             enc_proj)
        logits = self.readout(out)
        return new_state, logits

    # -- training -------------------------------------------------------------

    def forward(self, batch, train: bool = False):
        """batch: src [B,Ts], src_len [B], tgt [B,Tt] (bos-prefixed),
        tgt_len [B]. Returns per-example summed CE loss (masked)."""
        src, src_len = batch["src"], batch["src_len"]
        tgt, tgt_len = batch["tgt"], batch["tgt_len"]
        enc, enc_mask, dec0 = self.encode(src, src_len)
        with self.att.scope():
            enc_proj = self.att.proj_e(enc)
        tgt_in = tgt[:, :-1]
        tgt_out = tgt[:, 1:]
        y_embs = self.tgt_emb(tgt_in)                       # [B, Tt-1, E]

        # materialize decoder params before scan
        _ = self._dec_step(dec0, y_embs[:, 0], enc, enc_mask, enc_proj)

        def body(state, y_emb_t):
            new_state, out = self._dec_cell_step(state, y_emb_t, enc,
                                                 enc_mask, enc_proj)
            return new_state, out

        _, outs = lax.scan(body, dec0, jnp.swapaxes(y_embs, 0, 1))
        # one big [B*(Tt-1), h] @ [h, V] readout instead of Tt-1 tiny ones
        # inside the scan: same math, MXU-shaped (PERF.md "Round 5")
        logits = self.readout(jnp.swapaxes(outs, 0, 1))      # [B, Tt-1, V]
        losses = nn.costs.softmax_cross_entropy(logits, tgt_out)
        out_mask = length_mask(jnp.maximum(tgt_len - 1, 0), tgt_out.shape[1])
        return (losses * out_mask).sum(-1)

    def init_variables(self, rng, batch):
        return self.init(rng, batch)

    # -- generation (beam search) --------------------------------------------

    def generate(self, variables, src, src_len, beam_size: int = 4,
                 max_len: int = 32, length_penalty: float = 0.0):
        """Beam-search decode. Returns (tokens [B, beam, max_len],
        scores [B, beam]) sorted best-first. Jittable; the analog of
        ``RecurrentGradientMachine::generateSequence`` with ``--beam_size``."""
        return self.apply(variables, src, src_len, beam_size, max_len,
                          length_penalty, method="_beam_search")

    def _beam_search(self, src, src_len, K, max_len, length_penalty):
        B = src.shape[0]
        V = self.tgt_vocab
        enc, enc_mask, dec0 = self.encode(src, src_len)
        with self.att.scope():
            enc_proj = self.att.proj_e(enc)

        # expand to beams: [B*K, ...]
        def tile(x):
            return jnp.repeat(x, K, axis=0)

        enc_b, mask_b, proj_b = tile(enc), tile(enc_mask), tile(enc_proj)
        state = tile(dec0)

        neg_inf = -1e9
        # beam scores: beam 0 active, others dead (standard first-step trick)
        scores = jnp.tile(jnp.array([0.0] + [neg_inf] * (K - 1)), (B,))  # [B*K]
        tokens = jnp.full((B * K, max_len), PAD, jnp.int32)
        cur = jnp.full((B * K,), BOS, jnp.int32)
        finished = jnp.zeros((B * K,), bool)

        # materialize params (already created in encode/att) for the step
        _ = self._dec_step(state, self.tgt_emb(cur), enc_b, mask_b, proj_b)

        def body(carry, t):
            state, scores, tokens, cur, finished = carry
            new_state, logits = self._dec_step(state, self.tgt_emb(cur),
                                               enc_b, mask_b, proj_b)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)  # [B*K,V]
            # finished beams: only PAD continuation, score unchanged
            cont = jnp.where(finished[:, None],
                             jnp.where(jnp.arange(V)[None, :] == PAD, 0.0,
                                       neg_inf),
                             logp)
            cand = scores[:, None] + cont                   # [B*K, V]
            cand = cand.reshape(B, K * V)
            top_s, top_i = lax.top_k(cand, K)               # [B, K]
            beam_idx = top_i // V                           # which source beam
            tok = (top_i % V).astype(jnp.int32)
            flat_src = (jnp.arange(B)[:, None] * K + beam_idx).reshape(-1)
            new_state = jax.tree_util.tree_map(
                lambda s: jnp.take(s, flat_src, axis=0), new_state)
            tokens2 = jnp.take(tokens, flat_src, axis=0)
            tokens2 = tokens2.at[:, t].set(tok.reshape(-1))
            fin2 = jnp.take(finished, flat_src) | (tok.reshape(-1) == EOS)
            return (new_state, top_s.reshape(-1), tokens2, tok.reshape(-1),
                    fin2), None

        (state, scores, tokens, cur, finished), _ = lax.scan(
            body, (state, scores, tokens, cur, finished),
            jnp.arange(max_len))

        tokens = tokens.reshape(B, K, max_len)
        scores = scores.reshape(B, K)
        if length_penalty > 0:
            lengths = (tokens != PAD).sum(-1)
            scores = scores / ((5.0 + lengths) / 6.0) ** length_penalty
        order = jnp.argsort(-scores, axis=1)
        tokens = jnp.take_along_axis(tokens, order[..., None], axis=1)
        scores = jnp.take_along_axis(scores, order, axis=1)
        return tokens, scores
