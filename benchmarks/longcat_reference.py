"""The plain reference of LongCat-Flash's shortcut-connected double layer,
as one chip of an expert-parallel deployment holds it: the forward pass in
straightforward ``jax.numpy``, float32, ``highest`` matmul precision. No
kernels, no cache, no absorbed products, no grouped product, nothing
imported from the program under test. What an architecture does not
change (the seeded matrices and scales, the control's rounding, RMS norm,
rotary, the gated feed-forward, embedding and head, the padding of a
call's sequences) is ``benchmarks/pangu_reference.py``'s, as it stands.

The equations (``x`` the residual stream; ``RMS`` with a learned scale,
epsilon ``rms_norm_eps``, computed in float32; no biases but the router's
selection bias):

- one layer, a DOUBLE layer with four norms (none on a sublayer's
  output): ``h0 = x + MLA_0(RMS_a0(x))``; ``u = RMS_f0(h0)``;
  ``m = Experts(u)``; ``h1 = h0 + FFN_0(u)``;
  ``h2 = h1 + MLA_1(RMS_a1(h1))``; ``h3 = h2 + FFN_1(RMS_f1(h2))``;
  ``y = h3 + m`` (the shortcut: the expert layer reads the first
  feed-forward's input and joins after the second). After the last layer
  ``RMS`` and an untied head. No position table.
- latent attention: ``c_q = q_scale * RMS(x W_qa)`` with ``q_scale =
  sqrt(hidden / q_lora_rank)``, ``q = c_q W_qb``, a head's values split
  ``q_nope | q_rope``; ``[c_kv | k_r] = x W_kva``, ``c_kv <- kv_scale *
  RMS(c_kv)`` with ``kv_scale = sqrt(hidden / kv_lora_rank)``; ``k_rope =
  RoPE(k_r)`` (ONE key all heads share), ``q_rope <- RoPE(q_rope)``;
  ``[k_nope | v] = c_kv W_kvb`` a head; scores ``(q_nope . k_nope + q_rope
  . k_rope) / sqrt(nope + rope)``, causal softmax, weighted sum of ``v``,
  heads concatenated, ``W_o``.
- dense feed-forward: ``W_down(silu(z W_gate) * (z W_up))``.
- expert layer: ``p = softmax(u W_r)`` over ALL the router's outputs (the
  published real experts, then the identity experts); the ``k`` largest of
  ``p + b`` (``b`` the selection bias); gates ``g_e = scaling * p_e`` for
  the chosen, the bias not in them, not renormalised. A chosen real expert
  adds ``g_e Expert_e(u)``, a chosen identity expert ``g_e u``.
  ``Experts(u)`` here is the sum over the chosen experts HELD HERE (a loop
  over them, every token through every held expert, weighted by its gate
  or by nought) plus ALL identity terms; what the absent real experts
  would add is left out.

Rotary pairing: rotate-half, as ``pangu_reference._rope``.

``quant="int8"`` is the CONTROL (every product's operands rounded to int8,
``pangu_reference._q``); ``quant="bfloat16"`` a diagnosis. ``fault`` plants
a wrong BLOCK at full precision, the upper reading of the widest served
gap's limit: ``"no_identity"`` leaves the identity terms out of every
expert layer, ``"no_shortcut"`` feeds the expert layer the SECOND
feed-forward's input (the usual place of an expert layer) and not the
first's.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.pangu_reference import (HIGHEST, Q_CHUNK, _f32, _gated,
                                        _matrix, _mm, _q, _rms, _rope,
                                        _scale, embed, pad_sequence,
                                        readout, seed32, top_weights,
                                        widths)


class Dims(NamedTuple):
    """What the reference needs of a configuration file, hashable."""
    L: int              # double layers held here
    D: int
    H: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    F: int              # dense feed-forward width
    F_e: int            # one expert's width
    E: int              # real experts of a layer, as published
    Z: int              # identity experts after them in the router
    held_first: int     # real experts held here: ids held_first ..
    held: int           # .. held_first + held - 1
    K: int              # choices a token
    scaling: float
    q_scale: float      # constant factor on the normalised c_q
    kv_scale: float     # .. and on the normalised c_kv
    eps: float
    theta: float
    P: int              # positions a slot's table may cover
    V: int              # rows of the vocabulary held here
    norm_jitter: float
    bias_std: float     # the selection bias is bias_std * N(0, 1)


def dims(cfg: Dict[str, Any]) -> Dims:
    """Read a configuration file written with the source's own keys. The
    keys that count (layers, experts, vocabulary) give what is HELD HERE;
    ``published`` holds the source's counts, of which the router's width
    is the only one the arithmetic needs."""
    held = cfg["deployment"]["experts_held"]
    assert int(held[1]) == int(cfg["n_routed_experts"])
    assert cfg.get("zero_expert_type", "identity") == "identity"
    D = int(cfg["hidden_size"])
    factor = lambda on, rank: math.sqrt(D / rank) if on else 1.0
    return Dims(
        L=int(cfg["num_layers"]), D=D, H=int(cfg["num_attention_heads"]),
        q_rank=int(cfg["q_lora_rank"]), kv_rank=int(cfg["kv_lora_rank"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        v=int(cfg["v_head_dim"]), F=int(cfg["ffn_hidden_size"]),
        F_e=int(cfg["expert_ffn_hidden_size"]),
        E=int(cfg["published"]["n_routed_experts"]),
        Z=int(cfg["zero_expert_num"]),
        held_first=int(held[0]), held=int(held[1]),
        K=int(cfg["moe_topk"]),
        scaling=float(cfg["routed_scaling_factor"]),
        q_scale=factor(cfg["mla_scale_q_lora"], int(cfg["q_lora_rank"])),
        kv_scale=factor(cfg["mla_scale_kv_lora"], int(cfg["kv_lora_rank"])),
        eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
        P=int(cfg["max_position_embeddings"]), V=int(cfg["vocab_size"]),
        norm_jitter=float(cfg["assumed"]["norm_scale_jitter"]),
        bias_std=float(cfg["assumed"]["select_bias_std"]))


# ---------------------------------------------------------------------------
# weights: one double layer at a time, every value a bfloat16 number
# ---------------------------------------------------------------------------

def _attention_weights(z: Dims, k) -> Dict[str, Any]:
    """The two up-projections are N(0, 1 / (fan_in * factor ** 2)): the
    latent's constant factor times the matrix is N(0, 1 / fan_in), so a
    head's queries, keys and values have unit variance WITH the factors,
    which is what they are there for (``sqrt(hidden / rank)`` beside a
    matrix drawn for ``hidden`` inputs). Drawn for ``rank`` inputs the
    scores' spread is 5.7, the softmax all but one-hot, and every
    attention multiplies a rounding by three and more: the float32
    reference with bfloat16 operands then moves a logit by 0.33 after TWO
    double layers (my CPU run at the published widths, PR 33) and no
    limit could tell bfloat16 from int8."""
    H, D, j = z.H, z.D, z.norm_jitter
    return {
        "wq_a": _matrix(k[0], (D, z.q_rank), D),
        "q_norm": _scale(k[1], z.q_rank, j),
        "wq_b": _matrix(k[2], (z.q_rank, H * (z.nope + z.rope)),
                        z.q_rank * z.q_scale ** 2),
        "wkv_a": _matrix(k[3], (D, z.kv_rank + z.rope), D),
        "kv_norm": _scale(k[4], z.kv_rank, j),
        "wkv_b": _matrix(k[5], (z.kv_rank, H * (z.nope + z.v)),
                         z.kv_rank * z.kv_scale ** 2),
        "wo": _matrix(k[6], (H * z.v, D), H * z.v)}


def _dense_weights(z: Dims, k) -> Dict[str, Any]:
    return {"w_gate": _matrix(k[0], (z.D, z.F), z.D),
            "w_up": _matrix(k[1], (z.D, z.F), z.D),
            "w_down": _matrix(k[2], (z.F, z.D), z.F)}


def layer_weights(z: Dims, seed, i: int) -> Dict[str, Any]:
    """Double layer ``i``'s weights as bfloat16 arrays (``i`` static; trace
    it inside a jit, ``seed`` a uint32): two attentions, two dense
    feed-forwards, four norms, the router over ``E + Z`` outputs, its
    selection bias, the held experts. Matrices N(0, 1 / fan_in), norm
    scales 1 + ``norm_jitter`` N(0, 1), the bias ``bias_std`` N(0, 1)."""
    k = jax.random.split(jax.random.fold_in(
        jax.random.PRNGKey(jnp.asarray(seed, jnp.uint32)), i), 32)
    D, j = z.D, z.norm_jitter
    return {
        "n_a0": _scale(k[0], D, j), "n_f0": _scale(k[1], D, j),
        "n_a1": _scale(k[2], D, j), "n_f1": _scale(k[3], D, j),
        "attn0": _attention_weights(z, k[4:11]),
        "attn1": _attention_weights(z, k[11:18]),
        "ffn0": _dense_weights(z, k[18:21]),
        "ffn1": _dense_weights(z, k[21:24]),
        "router": _matrix(k[24], (D, z.E + z.Z), D),
        "bias": (z.bias_std * jax.random.normal(
            k[25], (z.E + z.Z,), jnp.float32)).astype(jnp.bfloat16),
        "e_gate": _matrix(k[26], (z.held, D, z.F_e), D),
        "e_up": _matrix(k[27], (z.held, D, z.F_e), D),
        "e_down": _matrix(k[28], (z.held, z.F_e, D), z.F_e)}


# ---------------------------------------------------------------------------
# the forward pass, one sequence ``x [T, D]`` at a time
# ---------------------------------------------------------------------------

def _attention(x, w, z: Dims, quant):
    T, H = x.shape[0], z.H
    pos = jnp.arange(T)
    c_q = z.q_scale * _rms(_mm(x, w["wq_a"], quant), w["q_norm"], z.eps)
    q = _mm(c_q, w["wq_b"], quant).reshape(T, H, z.nope + z.rope)
    kv = _mm(x, w["wkv_a"], quant)
    c_kv = z.kv_scale * _rms(kv[:, :z.kv_rank], w["kv_norm"], z.eps)
    k_rope = _rope(kv[:, z.kv_rank:], pos, z.theta)           # [T, rope]
    kvb = _mm(c_kv, w["wkv_b"], quant).reshape(T, H, z.nope + z.v)
    k = jnp.concatenate([kvb[..., :z.nope], jnp.broadcast_to(
        k_rope[:, None], (T, H, z.rope))], -1)
    q = jnp.concatenate([q[..., :z.nope],
                         _rope(q[..., z.nope:], pos, z.theta)], -1)
    q, k, v = _q(q, -1, quant), _q(k, -1, quant), _q(kvb[..., z.nope:], -1,
                                                     quant)
    scale = 1.0 / math.sqrt(z.nope + z.rope)

    def chunk(args):
        qc, pc = args                                   # [C, H, 192], [C]
        s = jnp.einsum("qhd,khd->hqk", qc, k, precision=HIGHEST) * scale
        s = jnp.where(pos[None, None, :] <= pc[None, :, None], s, -jnp.inf)
        p = _q(jax.nn.softmax(s, axis=-1), -1, quant)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    C = Q_CHUNK if T % Q_CHUNK == 0 else T
    o = lax.map(chunk, (q.reshape(T // C, C, H, -1), pos.reshape(T // C, C)))
    return _mm(o.reshape(T, H * z.v), w["wo"], quant)


def route(u, w, z: Dims, quant):
    """``(ids [T, K], gates [T, K])``: the ``K`` largest of ``softmax +
    bias`` over all ``E + Z`` outputs; gates the raw softmax scores times
    ``scaling``."""
    p = jax.nn.softmax(_mm(u, w["router"], quant), axis=-1)
    _, idx = lax.top_k(p + w["bias"], z.K)
    return idx, z.scaling * jnp.take_along_axis(p, idx, axis=-1)


FAULTS = ("no_identity", "no_shortcut")


def _experts(u, w, z: Dims, quant, identity: bool = True):
    """The held real experts' part of the routed sum (a loop over them,
    every token through each, weighted by the token's gate for it) plus
    every identity term ``g_e u``. Also returns the ids chosen."""
    idx, gates = route(u, w, z, quant)

    def one(y, e):
        g = jnp.sum(jnp.where(idx == z.held_first + e, gates, 0.0), -1)
        return y + g[:, None] * _gated(u, w["e_gate"][e], w["e_up"][e],
                                       w["e_down"][e], quant), None

    y = jnp.sum(jnp.where((idx >= z.E) & identity, gates, 0.0),
                -1)[:, None] * u
    y, _ = lax.scan(one, y, jnp.arange(z.held))
    return y, idx


def _dense(x, w, quant):
    return _gated(x, w["w_gate"], w["w_up"], w["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("z", "quant", "fault"))
def block(x, w, z: Dims, quant: Optional[str] = None,
          fault: Optional[str] = None):
    """One double layer on ``x [T, D]``; ``(y, chosen ids [T, K])``."""
    assert fault is None or fault in FAULTS, fault
    w = _f32(w)
    h = x + _attention(_rms(x, w["n_a0"], z.eps), w["attn0"], z, quant)
    u = _rms(h, w["n_f0"], z.eps)
    m, idx = _experts(u, w, z, quant, identity=fault != "no_identity")
    h = h + _dense(u, w["ffn0"], quant)
    h = h + _attention(_rms(h, w["n_a1"], z.eps), w["attn1"], z, quant)
    u = _rms(h, w["n_f1"], z.eps)
    if fault == "no_shortcut":
        m, idx = _experts(u, w, z, quant)
    h = h + _dense(u, w["ffn1"], quant)
    return h + m, idx


_layer_weights = jax.jit(layer_weights, static_argnames=("z", "i"))
_top_weights = jax.jit(top_weights, static_argnames=("z",))


def forward(cfg, seed: int, sequences, quant: Optional[str] = None,
            routing: Optional[list] = None, stream: Optional[list] = None,
            fault: Optional[str] = None):
    """``[(ids [T] int32, rows [n] int32)]`` -> float32 logits ``[n, V]``
    each, layer by layer over all sequences (one layer's weights alive at
    a time). ``routing``, a list, receives each layer's chosen ids ``[T,
    K]`` a sequence (numpy); ``stream`` the residual's RMS after each
    layer, over the first sequence."""
    with jax.default_matmul_precision(HIGHEST):
        z = dims(cfg)
        top = _top_weights(z, seed32(seed))
        xs = [embed(top, jnp.asarray(ids), z) for ids, _ in sequences]
        for i in range(z.L):
            w = _layer_weights(z, seed32(seed), i)
            picked = []
            for j, x in enumerate(xs):
                xs[j], idx = block(x, w, z, quant, fault)
                picked.append(idx)
            if routing is not None:
                routing.append([np.asarray(p) for p in picked])
            if stream is not None:
                stream.append(float(jnp.sqrt(jnp.mean(jnp.square(xs[0])))))
            del w
        return [np.asarray(readout(top, x, jnp.asarray(rows), z, quant))
                for x, (_, rows) in zip(xs, sequences)]


def serve_reference(cfg, seed: int, sequences, quant: Optional[str] = None,
                    fault: Optional[str] = None):
    """For each ``(prompt, tokens)``: the float32 logits at the positions
    that produced ``tokens``, as ``[n, V]`` arrays. With ``quant`` set the
    logits are the control's, with ``fault`` a wrong block's."""
    lengths = [len(p) + len(t) - 1 for p, t in sequences]
    padded = [pad_sequence(p, t, w)
              for (p, t), w in zip(sequences, widths(lengths))]
    out = forward(cfg, seed, padded, quant, fault=fault)
    return [o[:len(t)] for o, (_, t) in zip(out, sequences)]


def train_reference(cfg, seed, batches, opt, quant=None, fault=None):
    raise NotImplementedError(
        "this configuration is served, not trained: at 16 bytes a "
        "parameter no cut inside the guide's floors fits one chip")
