"""The plain reference of Laguna-S-2.1's decoder, as one chip of an
expert-parallel deployment holds it: the forward pass in straightforward
``jax.numpy``, float32, ``highest`` matmul precision. No kernels, no cache,
no grouped product, no online softmax, nothing imported from the program
under test. What an architecture does not change (the seeded matrices and
scales, the control's rounding, RMS norm, the gated feed-forward,
embedding and head, the padding of a call's sequences) is
``benchmarks/pangu_reference.py``'s, as it stands.

The equations (``x`` the float32 residual stream; ``RMS`` with a learned
scale, epsilon ``rms_norm_eps``; no biases):

- layer ``l``: ``h = x + Attn_l(RMS(x))``, ``y = h + MLP_l(RMS(h))``;
  token embeddings enter unscaled; after the last layer ``RMS`` and an
  untied head.
- ``Attn_l`` on ``z``: ``H_l = num_attention_heads_per_layer[l]`` query
  heads (48 where ``layer_types[l]`` is ``full_attention``, 72 where
  ``sliding_attention``), ``H_kv = 8`` KV heads, head size 128: ``q = z
  W_q [H_l, 128]``, ``k = z W_k``, ``v = z W_v [8, 128]``; query head ``h``
  reads KV head ``h // (H_l / 8)``. Rotary by layer kind
  (``rope_parameters``), rotate-half: a sliding layer turns all 128 values
  of a head at base 10,000; a full layer the first ``128 *
  partial_rotary_factor = 64`` (the others pass through) at YaRN's
  frequencies (:func:`yarn_inv_freq`) with cosines and sines multiplied by
  ``attention_factor``. Scores ``q . k / sqrt(128)``, causal; a sliding
  layer's query ``i`` sees keys ``i - sliding_window < j <= i``. Softmax
  in float32. Per-head gate: ``g = sigmoid(z W_g)``, ``W_g [hidden,
  H_l]``; head ``h``'s output times ``g_h``; heads concatenated, ``W_o``.
- ``MLP_l``, ``l`` in ``mlp_only_layers``: ``W_down(silu(u W_gate) * (u
  W_up))`` of width ``intermediate_size``. Otherwise ``s = softmax(u
  W_r)`` over ALL the router's outputs; the ``k`` largest; gates
  ``moe_routed_scaling_factor * s_e / (sum of the k)`` on the experts'
  outputs; ``MLP(u) = Shared(u) + sum g_e Expert_e(u)``, the sum over those
  of the token's ``k`` that are among the experts HELD HERE (a loop over
  them, every token through every held expert, weighted by its gate or by
  nought); the shared expert ungated; what the absent experts would add is
  left out.

``quant="int8"`` is the CONTROL (every product's operands rounded to int8,
``pangu_reference._q``); ``quant="bfloat16"`` a diagnosis. ``fault``
plants a wrong LAYER at full precision (:data:`FAULTS`): ``"no_window"``
lets the sliding layers attend to everything, ``"no_gate"`` sets every
head's gate to 1, ``"wrong_group"`` has query head ``h`` read KV head ``h %
8``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.pangu_reference import (HIGHEST, _f32, _gated, _matrix, _mm,
                                        _q, _rms, _scale, embed,
                                        pad_sequence, readout, seed32,
                                        top_weights, widths)

Q_CHUNK = 128          # query rows through attention at a time
FAULTS = ("no_window", "no_gate", "wrong_group")


class Rotary(NamedTuple):
    """One layer kind's rotary settings, hashable."""
    theta: float
    dim: int               # values of a head that turn
    factor: float          # YaRN: 1.0 = none
    original: int
    beta_fast: float
    beta_slow: float
    attention_factor: float


class Dims(NamedTuple):
    """What the reference needs of a configuration file, hashable."""
    L: int
    D: int
    heads: Tuple[int, ...]              # query heads, a layer
    windows: Tuple[Optional[int], ...]  # None: a full layer
    dense: Tuple[bool, ...]             # a dense feed-forward, a layer
    H_kv: int
    hd: int
    F: int                  # dense feed-forward width
    F_e: int                # one expert's width
    F_s: int                # the shared expert's width
    E: int                  # the router's outputs, as published
    held_first: int
    held: int
    K: int
    scaling: float
    eps: float
    rope_full: Rotary
    rope_window: Rotary
    P: int
    V: int
    norm_jitter: float


def _rotary(p: Dict[str, Any], head_dim: int) -> Rotary:
    yarn = p.get("rope_type", "default") == "yarn"
    return Rotary(
        theta=float(p["rope_theta"]),
        dim=int(head_dim * float(p.get("partial_rotary_factor", 1))),
        factor=float(p["factor"]) if yarn else 1.0,
        original=int(p.get("original_max_position_embeddings", 0)),
        beta_fast=float(p.get("beta_fast", 32)),
        beta_slow=float(p.get("beta_slow", 1)),
        attention_factor=float(p.get("attention_factor", 1.0))
        if yarn else 1.0)


def dims(cfg: Dict[str, Any]) -> Dims:
    """Read a configuration file written with the source's own keys. The
    keys that count (layers, experts) give what is HELD HERE; the per-layer
    lists are the source's, whole, of which the first ``num_hidden_layers``
    entries are this stage's layers; ``published`` holds the source's
    counts, of which the router's width is the only one the arithmetic
    needs."""
    held = cfg["deployment"]["experts_held"]
    assert int(held[1]) == int(cfg["num_experts"])
    assert cfg["gating"] == "per-head" and cfg["norm_topk_prob"]
    assert not cfg["moe_apply_router_weight_on_input"]
    assert not cfg["moe_router_logit_softcapping"]
    assert int(cfg["decoder_sparse_step"]) == 1
    L, hd = int(cfg["num_hidden_layers"]), int(cfg["head_dim"])
    kinds = cfg["layer_types"][:L]
    assert set(kinds) <= {"full_attention", "sliding_attention"}
    return Dims(
        L=L, D=int(cfg["hidden_size"]),
        heads=tuple(int(h) for h in
                    cfg["num_attention_heads_per_layer"][:L]),
        windows=tuple(int(cfg["sliding_window"])
                      if k == "sliding_attention" else None for k in kinds),
        dense=tuple(i in cfg["mlp_only_layers"] for i in range(L)),
        H_kv=int(cfg["num_key_value_heads"]), hd=hd,
        F=int(cfg["intermediate_size"]),
        F_e=int(cfg["moe_intermediate_size"]),
        F_s=int(cfg["shared_expert_intermediate_size"]),
        E=int(cfg["published"]["num_experts"]),
        held_first=int(held[0]), held=int(held[1]),
        K=int(cfg["num_experts_per_tok"]),
        scaling=float(cfg["moe_routed_scaling_factor"]),
        eps=float(cfg["rms_norm_eps"]),
        rope_full=_rotary(cfg["rope_parameters"]["full_attention"], hd),
        rope_window=_rotary(cfg["rope_parameters"]["sliding_attention"], hd),
        P=int(cfg["max_position_embeddings"]), V=int(cfg["vocab_size"]),
        norm_jitter=float(cfg["assumed"]["norm_scale_jitter"]))


# ---------------------------------------------------------------------------
# weights: one layer at a time, every value a bfloat16 number
# ---------------------------------------------------------------------------

def layer_weights(z: Dims, seed, i: int) -> Dict[str, Any]:
    """Layer ``i``'s weights as bfloat16 arrays (``i`` static; trace it
    inside a jit, ``seed`` a uint32). Matrices N(0, 1 / fan_in), norm
    scales 1 + ``norm_jitter`` N(0, 1): queries, keys, values, gate logits
    and router logits have unit variance at any width."""
    k = jax.random.split(jax.random.fold_in(
        jax.random.PRNGKey(jnp.asarray(seed, jnp.uint32)), i), 16)
    D, H, hd, j = z.D, z.heads[i], z.hd, z.norm_jitter
    w = {"n_attn": _scale(k[0], D, j), "n_mlp": _scale(k[1], D, j),
         "wq": _matrix(k[2], (D, H * hd), D),
         "wk": _matrix(k[3], (D, z.H_kv * hd), D),
         "wv": _matrix(k[4], (D, z.H_kv * hd), D),
         "wg": _matrix(k[5], (D, H), D),
         "wo": _matrix(k[6], (H * hd, D), H * hd)}
    if z.dense[i]:
        w.update(w_gate=_matrix(k[7], (D, z.F), D),
                 w_up=_matrix(k[8], (D, z.F), D),
                 w_down=_matrix(k[9], (z.F, D), z.F))
    else:
        w.update(router=_matrix(k[10], (D, z.E), D),
                 e_gate=_matrix(k[11], (z.held, D, z.F_e), D),
                 e_up=_matrix(k[12], (z.held, D, z.F_e), D),
                 e_down=_matrix(k[13], (z.held, z.F_e, D), z.F_e),
                 s_gate=_matrix(k[14], (D, z.F_s), D),
                 s_up=_matrix(k[15], (D, z.F_s), D),
                 s_down=_matrix(jax.random.fold_in(k[15], 1),
                                (z.F_s, D), z.F_s))
    return w


# ---------------------------------------------------------------------------
# the forward pass, one sequence ``x [T, D]`` at a time
# ---------------------------------------------------------------------------

def yarn_inv_freq(r: Rotary) -> np.ndarray:
    """The ``dim / 2`` inverse frequencies of a layer kind. Plain:
    ``theta ** (-2 i / dim)``. YaRN: frequency ``i`` is a blend of that
    (weight ``1 - ramp_i``) and of that over ``factor`` (weight
    ``ramp_i``), ``ramp`` the linear ramp from 0 at the correction
    dimension of ``beta_fast`` rotations (rounded down) to 1 at that of
    ``beta_slow`` (rounded up), where the correction dimension of ``n``
    rotations over the original length is ``dim log(original / (2 pi n))
    / (2 log theta)``."""
    i = np.arange(0, r.dim, 2, dtype=np.float64)
    plain = r.theta ** (-i / r.dim)
    if r.factor == 1.0:
        return plain.astype(np.float32)
    corr = lambda n: r.dim * math.log(r.original / (n * 2 * math.pi)) \
        / (2 * math.log(r.theta))
    low = max(math.floor(corr(r.beta_fast)), 0)
    high = min(math.ceil(corr(r.beta_slow)), r.dim - 1)
    high = high + 0.001 if low == high else high
    ramp = np.clip((np.arange(r.dim // 2) - low) / (high - low), 0.0, 1.0)
    return (plain * (1 - ramp) + plain / r.factor * ramp).astype(np.float32)


def _rope(x, pos, r: Rotary):
    """``x [T, H, d]``: the first ``r.dim`` values of a head turned by the
    row's position (rotate-half among themselves, cosines and sines times
    the attention factor), the others passed through."""
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(yarn_inv_freq(r))
    cos = (jnp.cos(ang) * r.attention_factor)[:, None]
    sin = (jnp.sin(ang) * r.attention_factor)[:, None]
    a, b = x[..., :r.dim // 2], x[..., r.dim // 2:r.dim]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., r.dim:]], -1)


def _attention(x, w, z: Dims, i: int, quant, fault=None):
    T, H, Hk, hd = x.shape[0], z.heads[i], z.H_kv, z.hd
    window = None if fault == "no_window" else z.windows[i]
    r = z.rope_full if z.windows[i] is None else z.rope_window
    pos = jnp.arange(T)
    q = _rope(_mm(x, w["wq"], quant).reshape(T, H, hd), pos, r)
    k = _rope(_mm(x, w["wk"], quant).reshape(T, Hk, hd), pos, r)
    v = _mm(x, w["wv"], quant).reshape(T, Hk, hd)
    # the KV head each query head reads
    reads = (np.arange(H) % Hk if fault == "wrong_group"
             else np.arange(H) // (H // Hk))
    q = _q(q, -1, quant)
    k, v = _q(k, -1, quant)[:, reads], _q(v, -1, quant)[:, reads]
    scale = 1.0 / math.sqrt(hd)

    C = Q_CHUNK if T % Q_CHUNK == 0 else T
    # a sliding layer's chunk of queries is handed the keys its window can
    # reach and no others (``C + window`` rows from ``window`` before the
    # chunk on): the same scores and the same mask over fewer masked keys
    near = window is not None and C + window < T

    def chunk(args):
        qc, pc = args                                   # [C, H, hd], [C]
        kc, vc, kp = k, v, pos
        if near:
            lo = jnp.clip(pc[0] - window, 0, T - (C + window))
            kc = lax.dynamic_slice_in_dim(k, lo, C + window)
            vc = lax.dynamic_slice_in_dim(v, lo, C + window)
            kp = lo + jnp.arange(C + window)
        s = jnp.einsum("qhd,khd->hqk", qc, kc, precision=HIGHEST) * scale
        sees = kp[None, :] <= pc[:, None]
        if window is not None:
            sees &= kp[None, :] > pc[:, None] - window
        s = jnp.where(sees[None], s, -jnp.inf)
        p = _q(jax.nn.softmax(s, axis=-1), -1, quant)
        return jnp.einsum("hqk,khd->qhd", p, vc, precision=HIGHEST)

    o = lax.map(chunk, (q.reshape(T // C, C, H, hd), pos.reshape(T // C, C)))
    o = o.reshape(T, H, hd)
    if fault != "no_gate":
        o = o * jax.nn.sigmoid(_mm(x, w["wg"], quant))[:, :, None]
    return _mm(o.reshape(T, H * hd), w["wo"], quant)


def route(u, w, z: Dims, quant):
    """``(ids [T, K], gates [T, K])``: the ``K`` largest of the softmax
    over all ``E`` outputs; gates those scores over their sum, times
    ``scaling``."""
    s = jax.nn.softmax(_mm(u, w["router"], quant), axis=-1)
    top, idx = lax.top_k(s, z.K)
    return idx, z.scaling * top / jnp.sum(top, -1, keepdims=True)


def _experts(u, w, z: Dims, quant):
    """The shared expert plus the held experts' part of the routed sum (a
    loop over them, every token through each, weighted by the token's
    gate for it). Also returns the ids chosen."""
    idx, gates = route(u, w, z, quant)

    def one(y, e):
        g = jnp.sum(jnp.where(idx == z.held_first + e, gates, 0.0), -1)
        return y + g[:, None] * _gated(u, w["e_gate"][e], w["e_up"][e],
                                       w["e_down"][e], quant), None

    y = _gated(u, w["s_gate"], w["s_up"], w["s_down"], quant)
    y, _ = lax.scan(one, y, jnp.arange(z.held))
    return y, idx


@functools.partial(jax.jit, static_argnames=("z", "i", "quant", "fault"))
def block(x, w, z: Dims, i: int, quant: Optional[str] = None,
          fault: Optional[str] = None):
    """Layer ``i`` on ``x [T, D]``; ``(y, chosen ids [T, K] or None)``."""
    assert fault is None or fault in FAULTS, fault
    w = _f32(w)
    h = x + _attention(_rms(x, w["n_attn"], z.eps), w, z, i, quant, fault)
    u = _rms(h, w["n_mlp"], z.eps)
    if z.dense[i]:
        return h + _gated(u, w["w_gate"], w["w_up"], w["w_down"],
                          quant), None
    f, idx = _experts(u, w, z, quant)
    return h + f, idx


_layer_weights = jax.jit(layer_weights, static_argnames=("z", "i"))
_top_weights = jax.jit(top_weights, static_argnames=("z",))


def forward(cfg, seed: int, sequences, quant: Optional[str] = None,
            routing: Optional[list] = None, stream: Optional[list] = None,
            fault: Optional[str] = None):
    """``[(ids [T] int32, rows [n] int32)]`` -> float32 logits ``[n, V]``
    each, layer by layer over all sequences (one layer's weights alive at
    a time). ``routing``, a list, receives each expert layer's chosen ids
    ``[T, K]`` a sequence (numpy); ``stream`` the residual's RMS after
    each layer, over the first sequence."""
    with jax.default_matmul_precision(HIGHEST):
        z = dims(cfg)
        top = _top_weights(z, seed32(seed))
        xs = [embed(top, jnp.asarray(ids), z) for ids, _ in sequences]
        for i in range(z.L):
            w = _layer_weights(z, seed32(seed), i)
            picked = []
            for j, x in enumerate(xs):
                xs[j], idx = block(x, w, z, i, quant, fault)
                picked.append(idx)
            if routing is not None and picked[0] is not None:
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
    logits are the control's, with ``fault`` a wrong layer's."""
    lengths = [len(p) + len(t) - 1 for p, t in sequences]
    padded = [pad_sequence(p, t, w)
              for (p, t), w in zip(sequences, widths(lengths))]
    out = forward(cfg, seed, padded, quant, fault=fault)
    return [o[:len(t)] for o, (_, t) in zip(out, sequences)]


def train_reference(cfg, seed, batches, opt, quant=None, fault=None):
    raise NotImplementedError(
        "this configuration is served, not trained: at 16 bytes a "
        "parameter no cut inside the guide's floors fits one chip")
