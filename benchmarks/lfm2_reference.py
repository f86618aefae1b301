"""The plain reference of LFM2-24B-A2B's decoder, as one pipeline stage
that holds every expert runs it: the forward pass in straightforward
``jax.numpy``, float32, ``highest`` matmul precision. No kernels, no cache,
no state carried between calls, no grouped product, no online softmax,
nothing imported from the program under test. What an architecture does
not change (the seeded matrices and scales, the control's rounding, RMS
norm, the gated feed-forward, embedding and head, the padding of a call's
sequences) is ``benchmarks/pangu_reference.py``'s, as it stands.

The equations (``x`` the float32 residual stream; ``RMS`` with a learned
scale, epsilon ``norm_eps``; no biases):

- layer ``l``: ``h = x + Mix_l(RMS(x))``, ``y = h + FFN_l(RMS(h))``;
  token embeddings enter unscaled; after the last layer ``RMS`` and an
  untied head.
- ``Mix_l`` where ``layer_types[l]`` is ``conv``, on ``z``: ``B, C, x~ =
  split3(z W_in)`` (``W_in [D, 3D]``), ``u = B * x~``, ``v_t = sum_{j=0..2}
  w_j * u_{t-2+j}`` (depthwise, causal, ``conv_L_cache`` 3 taps, ``w [D,
  3]``, rows before the first zeros), ``(C * v) W_out``.
- ``Mix_l`` where it is ``full_attention``: 32 query heads on 8 KV heads of
  ``hidden_size / num_attention_heads = 64``: ``q = z W_q``, ``k = z W_k``,
  ``v = z W_v``; q and k each RMS-normalised a head over its 64 values
  (a learned scale of 64 a norm, epsilon ``norm_eps``); rotary over the
  whole head at ``rope_theta``, rotate-half; query head ``h`` reads KV
  head ``h // 4``; scores ``q . k / 8``, causal, softmax in float32;
  ``W_o``.
- ``FFN_l``, ``l < num_dense_layers``: ``W_down(silu(u W_gate) * (u
  W_up))`` of width ``intermediate_size``. Otherwise ``s = sigmoid(u
  W_r)`` over the 64 experts; the 4 chosen by ``s + b`` (``b`` the expert
  bias, ``use_expert_bias``), which moves the choice only; gates
  ``routed_scaling_factor * s_e / (sum of the 4 + 1e-6)``
  (``norm_topk_prob``); ``FFN(u) = sum g_e Expert_e(u)`` (a loop over the
  experts held, every token through each, weighted by its gate or by
  nought), each expert a SiLU-gated feed-forward of width
  ``moe_intermediate_size``; no shared expert.

Departures from the source: none in the equations; the sizes the row
does not give are the configuration file's ``assumed`` (an untied head,
the initial values).

``quant="int8"`` is the CONTROL (every product's operands rounded to
int8, ``pangu_reference._q``, and the convolution's input rows so); ``fault``
plants a wrong LAYER at full precision (:data:`FAULTS`): ``"no_b_gate"``
convolves ``x~`` and not ``B * x~``; ``"no_qk_norm"`` leaves q and k
unnormalised; ``"bias_in_gates"`` lets the expert bias weigh the gates
too (``s_e + b_e`` in the gates' numerators and sum); ``"swapped_pair"``
has the query heads of KV head ``2p`` read ``2p + 1`` and those of ``2p +
1`` read ``2p``; ``"no_state_carry"`` starts each prefill chunk's
convolution from zeros (``chunk`` positions a chunk, the cells' 512: a
position's taps do not reach behind its chunk's first position in the
prompt; decoded positions continue the last chunk's).
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
FAULTS = ("no_b_gate", "no_qk_norm", "bias_in_gates", "swapped_pair",
          "no_state_carry")
# the prefill chunk of the cells, where ``no_state_carry`` cuts a prompt
CHUNK = 512


class Dims(NamedTuple):
    """What the reference needs of a configuration file, hashable."""
    L: int
    D: int
    kinds: Tuple[str, ...]  # "conv" or "full", a layer
    dense: Tuple[bool, ...]  # a dense feed-forward, a layer
    H: int
    H_kv: int
    hd: int
    F: int                  # dense feed-forward width
    F_e: int                # one expert's width
    E: int                  # the router's outputs
    held_first: int
    held: int
    K: int
    scaling: float
    eps: float
    router_eps: float
    conv_k: int
    theta: float
    P: int
    V: int
    norm_jitter: float
    bias_std: float


def dims(cfg: Dict[str, Any]) -> Dims:
    """Read a configuration file written with the source's own keys: the
    first ``num_hidden_layers`` entries of ``layer_types`` are this
    stage's layers, the first ``num_dense_layers`` of them dense."""
    held = cfg["deployment"]["experts_held"]
    assert cfg["norm_topk_prob"] and cfg["use_expert_bias"]
    assert not cfg["conv_bias"]
    assert cfg["rope_parameters"]["rope_type"] == "default"
    L, D = int(cfg["num_hidden_layers"]), int(cfg["hidden_size"])
    H = int(cfg["num_attention_heads"])
    kinds = tuple({"conv": "conv", "full_attention": "full"}[t]
                  for t in cfg["layer_types"][:L])
    assert len(kinds) == L
    return Dims(
        L=L, D=D, kinds=kinds,
        dense=tuple(i < int(cfg["num_dense_layers"]) for i in range(L)),
        H=H, H_kv=int(cfg["num_key_value_heads"]), hd=D // H,
        F=int(cfg["intermediate_size"]),
        F_e=int(cfg["moe_intermediate_size"]),
        E=int(cfg["num_experts"]), held_first=int(held[0]),
        held=int(held[1]), K=int(cfg["num_experts_per_tok"]),
        scaling=float(cfg["routed_scaling_factor"]),
        eps=float(cfg["norm_eps"]),
        router_eps=float(cfg["assumed"]["router_eps"]),
        conv_k=int(cfg["conv_L_cache"]),
        theta=float(cfg["rope_parameters"]["rope_theta"]),
        P=int(cfg["max_position_embeddings"]), V=int(cfg["vocab_size"]),
        norm_jitter=float(cfg["assumed"]["norm_scale_jitter"]),
        bias_std=float(cfg["assumed"]["expert_bias_std"]))


# ---------------------------------------------------------------------------
# weights: one layer at a time, every value a bfloat16 number
# ---------------------------------------------------------------------------

def layer_weights(z: Dims, seed, i: int) -> Dict[str, Any]:
    """Layer ``i``'s weights as bfloat16 arrays (``i`` static; trace it
    inside a jit, ``seed`` a uint32). Matrices N(0, 1 / fan_in), the
    convolution's taps N(0, 1 / 3), norm scales 1 + ``norm_jitter`` N(0,
    1), the expert bias ``bias_std`` N(0, 1): the convolution's ``u`` and
    output, queries, keys and router logits have unit variance at any
    width."""
    k = jax.random.split(jax.random.fold_in(
        jax.random.PRNGKey(jnp.asarray(seed, jnp.uint32)), i), 16)
    D, j = z.D, z.norm_jitter
    w = {"n_mix": _scale(k[0], D, j), "n_ffn": _scale(k[1], D, j)}
    if z.kinds[i] == "conv":
        w.update(w_in=_matrix(k[2], (D, 3 * D), D),
                 conv=_matrix(k[3], (D, z.conv_k), z.conv_k),
                 w_out=_matrix(k[4], (D, D), D))
    else:
        w.update(wq=_matrix(k[2], (D, z.H * z.hd), D),
                 wk=_matrix(k[3], (D, z.H_kv * z.hd), D),
                 wv=_matrix(k[4], (D, z.H_kv * z.hd), D),
                 wo=_matrix(k[5], (z.H * z.hd, D), z.H * z.hd),
                 qn=_scale(k[6], z.hd, j), kn=_scale(k[7], z.hd, j))
    if z.dense[i]:
        w.update(w_gate=_matrix(k[8], (D, z.F), D),
                 w_up=_matrix(k[9], (D, z.F), D),
                 w_down=_matrix(k[10], (z.F, D), z.F))
    else:
        w.update(router=_matrix(k[11], (D, z.E), D),
                 bias=(z.bias_std * jax.random.normal(
                     k[12], (z.E,), jnp.float32)).astype(jnp.bfloat16),
                 e_gate=_matrix(k[13], (z.held, D, z.F_e), D),
                 e_up=_matrix(k[14], (z.held, D, z.F_e), D),
                 e_down=_matrix(k[15], (z.held, z.F_e, D), z.F_e))
    return w


# ---------------------------------------------------------------------------
# the forward pass, one sequence ``x [T, D]`` at a time
# ---------------------------------------------------------------------------

def _short_conv(x, w, z: Dims, seg, quant, fault=None):
    """``x [T, D]``; ``seg [T]``: a tap reaches row ``s`` from row ``t``
    only where ``seg[s] == seg[t]`` (all one segment but under
    ``no_state_carry``)."""
    T = x.shape[0]
    b, c, xt = jnp.split(_mm(x, w["w_in"], quant), 3, -1)
    u = _q(xt if fault == "no_b_gate" else b * xt, -1, quant)
    v = jnp.zeros_like(u)
    for j in range(z.conv_k):
        back = z.conv_k - 1 - j                  # tap j reads u_{t - back}
        rows = jnp.pad(u, ((back, 0), (0, 0)))[:T]
        same = jnp.pad(seg, (back, 0), constant_values=-1)[:T] == seg
        v = v + jnp.where(same[:, None], rows, 0.0) * w["conv"][:, j]
    return _mm(c * v, w["w_out"], quant)


def _rope(x, pos, theta):
    """``x [T, H, d]`` rotated by its row's position, rotate-half over
    the whole head."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def _attention(x, w, z: Dims, quant, fault=None):
    T, H, Hk, hd = x.shape[0], z.H, z.H_kv, z.hd
    pos = jnp.arange(T)
    q = _mm(x, w["wq"], quant).reshape(T, H, hd)
    k = _mm(x, w["wk"], quant).reshape(T, Hk, hd)
    v = _mm(x, w["wv"], quant).reshape(T, Hk, hd)
    if fault != "no_qk_norm":
        q, k = _rms(q, w["qn"], z.eps), _rms(k, w["kn"], z.eps)
    q, k = _rope(q, pos, z.theta), _rope(k, pos, z.theta)
    # the KV head each query head reads
    reads = np.arange(H) // (H // Hk)
    if fault == "swapped_pair":
        reads = reads ^ 1
    q = _q(q, -1, quant)
    k, v = _q(k, -1, quant)[:, reads], _q(v, -1, quant)[:, reads]
    scale = 1.0 / math.sqrt(hd)
    C = Q_CHUNK if T % Q_CHUNK == 0 else T

    def chunk(args):
        qc, pc = args                                   # [C, H, hd], [C]
        s = jnp.einsum("qhd,khd->hqk", qc, k, precision=HIGHEST) * scale
        s = jnp.where((pos[None, :] <= pc[:, None])[None], s, -jnp.inf)
        p = _q(jax.nn.softmax(s, axis=-1), -1, quant)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    o = lax.map(chunk, (q.reshape(T // C, C, H, hd), pos.reshape(T // C, C)))
    return _mm(o.reshape(T, H * hd), w["wo"], quant)


def route(u, w, z: Dims, quant, fault=None):
    """``(ids [T, K], gates [T, K])``: the ``K`` largest of ``s + b`` over
    all ``E`` sigmoid scores ``s``; gates ``s`` of those over their sum
    plus ``router_eps``, times ``scaling``."""
    s = jax.nn.sigmoid(_mm(u, w["router"], quant))
    _, idx = lax.top_k(s + w["bias"], z.K)
    top = jnp.take_along_axis(s + w["bias"] if fault == "bias_in_gates"
                              else s, idx, axis=-1)
    return idx, z.scaling * top / (jnp.sum(top, -1, keepdims=True)
                                   + z.router_eps)


def _experts(u, w, z: Dims, quant, fault=None):
    """The held experts' part of the routed sum (a loop over them, every
    token through each, weighted by the token's gate for it). Also
    returns the ids chosen."""
    idx, gates = route(u, w, z, quant, fault)

    def one(y, e):
        g = jnp.sum(jnp.where(idx == z.held_first + e, gates, 0.0), -1)
        return y + g[:, None] * _gated(u, w["e_gate"][e], w["e_up"][e],
                                       w["e_down"][e], quant), None

    y, _ = lax.scan(one, jnp.zeros_like(u), jnp.arange(z.held))
    return y, idx


@functools.partial(jax.jit, static_argnames=("z", "i", "quant", "fault"))
def block(x, w, seg, z: Dims, i: int, quant: Optional[str] = None,
          fault: Optional[str] = None):
    """Layer ``i`` on ``x [T, D]`` (``seg [T]``: see :func:`_short_conv`);
    ``(y, chosen ids [T, K] or None)``."""
    assert fault is None or fault in FAULTS, fault
    w = _f32(w)
    m = _rms(x, w["n_mix"], z.eps)
    h = x + (_short_conv(m, w, z, seg, quant, fault) if z.kinds[i] == "conv"
             else _attention(m, w, z, quant, fault))
    u = _rms(h, w["n_ffn"], z.eps)
    if z.dense[i]:
        return h + _gated(u, w["w_gate"], w["w_up"], w["w_down"],
                          quant), None
    f, idx = _experts(u, w, z, quant, fault)
    return h + f, idx


_layer_weights = jax.jit(layer_weights, static_argnames=("z", "i"))
_top_weights = jax.jit(top_weights, static_argnames=("z",))


# the layers each fault plants itself in (the others run as compiled for
# the reference)
_FAULT_LAYERS = {"no_b_gate": lambda z, i: z.kinds[i] == "conv",
                 "no_qk_norm": lambda z, i: z.kinds[i] == "full",
                 "swapped_pair": lambda z, i: z.kinds[i] == "full",
                 "bias_in_gates": lambda z, i: not z.dense[i]}


def segments(width: int, prompt_len: Optional[int], chunk: int = CHUNK):
    """The convolution's segments of a sequence of ``width`` rows: one,
    or with ``prompt_len`` (``no_state_carry``) one a prefill chunk of the
    prompt, the positions after it in the last."""
    if prompt_len is None:
        return np.zeros((width,), np.int32)
    pos = np.minimum(np.arange(width), prompt_len - 1)
    return (pos // chunk).astype(np.int32)


def forward(cfg, seed: int, sequences, quant: Optional[str] = None,
            routing: Optional[list] = None, fault: Optional[str] = None,
            prompt_lens=None, chunk: int = CHUNK):
    """``[(ids [T] int32, rows [n] int32)]`` -> float32 logits ``[n, V]``
    each, layer by layer over all sequences (one layer's weights alive at
    a time). ``routing``, a list, receives each expert layer's chosen ids
    ``[T, K]`` a sequence (numpy). ``prompt_lens`` (with ``fault``
    ``no_state_carry``) place each prompt's chunks of ``chunk``."""
    assert fault is None or fault in FAULTS, fault
    carry = fault == "no_state_carry"
    with jax.default_matmul_precision(HIGHEST):
        z = dims(cfg)
        top = _top_weights(z, seed32(seed))
        xs = [embed(top, jnp.asarray(ids), z) for ids, _ in sequences]
        segs = [jnp.asarray(segments(len(ids), p if carry else None, chunk))
                for (ids, _), p in zip(sequences,
                                       prompt_lens or [None] * len(xs))]
        for i in range(z.L):
            w = _layer_weights(z, seed32(seed), i)
            picked = []
            planted = fault if fault in _FAULT_LAYERS \
                and _FAULT_LAYERS[fault](z, i) else None
            for j, x in enumerate(xs):
                xs[j], idx = block(x, w, segs[j], z, i, quant, planted)
                picked.append(idx)
            if routing is not None and picked[0] is not None:
                routing.append([np.asarray(p) for p in picked])
            del w
        return [np.asarray(readout(top, x, jnp.asarray(rows), z, quant))
                for x, (_, rows) in zip(xs, sequences)]


def serve_reference(cfg, seed: int, sequences, quant: Optional[str] = None,
                    fault: Optional[str] = None, chunk: int = CHUNK):
    """For each ``(prompt, tokens)``: the float32 logits at the positions
    that produced ``tokens``, as ``[n, V]`` arrays. With ``quant`` set the
    logits are the control's, with ``fault`` a wrong layer's."""
    lengths = [len(p) + len(t) - 1 for p, t in sequences]
    padded = [pad_sequence(p, t, w)
              for (p, t), w in zip(sequences, widths(lengths))]
    out = forward(cfg, seed, padded, quant, fault=fault,
                  prompt_lens=[len(p) for p, _ in sequences], chunk=chunk)
    return [o[:len(t)] for o, (_, t) in zip(out, sequences)]


def train_reference(cfg, seed, batches, opt, quant=None, fault=None):
    raise NotImplementedError(
        "this configuration is served, not trained: a chip holds 8 of a "
        "layer's 64 experts for training, and the expert layer has no "
        "backward pass")
