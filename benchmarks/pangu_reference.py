"""The plain reference of the latent-attention expert model
(openPangu-Ultra-MoE's block, as one chip of an expert-parallel
deployment holds it): the forward pass in straightforward ``jax.numpy``,
float32, ``highest`` matmul precision. No kernels, no cache, no absorbed
products, no grouped product, nothing imported from the program under test.

The equations (``x`` the residual stream; ``RMS`` with a learned scale,
computed in float32):

- block, ``sandwich_norm``: ``h = x + RMS(Attn(RMS(x)))``,
  ``y = h + RMS(FFN(RMS(h)))``; after the last block ``RMS`` and an
  untied head. No position table.
- latent attention, no biases: ``c_q = RMS(x W_qa)``, ``q = c_q W_qb``, a
  head's values split into ``q_nope | q_rope``; ``[c_kv | k_r] = x W_kva``,
  ``c_kv <- RMS(c_kv)``, ``k_rope = RoPE(k_r)`` (ONE key all heads share),
  ``q_rope <- RoPE(q_rope)``; ``[k_nope | v] = c_kv W_kvb`` a head;
  scores ``(q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)``,
  causal softmax, weighted sum of ``v``, heads concatenated, ``W_o``.
- dense feed-forward: ``W_down(silu(x W_gate) * (x W_up))``.
- expert layer: ``s = sigmoid(x W_g)`` over ALL experts of the published
  router; the ``k`` largest; gates ``scaling * s_e / (sum of the k +
  1e-20)``; ``FFN(x) = Shared(x) + sum g_e Expert_e(x)``, the sum over those
  of the token's ``k`` that are among the experts HELD HERE (a loop over
  them, every token through every held expert, weighted by its gate or by
  nought); what the absent experts would add is left out.

Rotary pairing: rotate-half (column ``i`` with ``i + d/2``), angle
``position * theta ** (-2 i / d)``.

Weights come from :func:`layer_weights`, made from the seed one layer at
a time: every value is a bfloat16 number (what the program holds), here
upcast to float32. One layer's float32 weights are alive at a time (a
dense layer 2.5 GB, an expert layer 4.0 GB at the published widths), and
queries go through attention a chunk at a time.

``quant="int8"`` is the CONTROL, not a reference: every product's
operands rounded to int8 with one scale a row (activations, keys, values,
probabilities) or a column (weights), the next precision below the
bfloat16 the configuration states. ``quant="bfloat16"`` rounds the same
operands to bfloat16: the program's own precision in the reference's
arithmetic, a diagnosis that tells what of a gap is precision and what the
program's.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = "highest"
Q_CHUNK = 256          # query rows through attention at a time
ROW_QUANTUM = 1024     # a call's longest sequence is padded to a multiple


class Dims(NamedTuple):
    """What the reference needs of a configuration file, hashable."""
    L: int              # layers held here
    L_dense: int        # of which leading dense layers
    D: int
    H: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    F: int              # dense feed-forward width
    F_e: int            # one expert's width
    E: int              # the router's outputs (all experts of a layer)
    held_first: int     # experts held here: ids held_first ..
    held: int           # .. held_first + held - 1
    K: int              # experts a token
    shared: int
    scaling: float
    eps: float
    theta: float
    P: int              # positions a slot's table may cover
    V: int              # rows of the vocabulary held here
    norm_jitter: float


def dims(cfg: Dict[str, Any]) -> Dims:
    """Read a configuration file written with the source's own keys. The
    keys that count (layers, experts, vocabulary) give what is HELD
    HERE; ``published`` holds the source's counts, of which the router's
    width is the only one the arithmetic needs."""
    held = cfg["deployment"]["experts_held"]
    assert int(held[1]) == int(cfg["n_routed_experts"])
    return Dims(
        L=int(cfg["num_hidden_layers"]),
        L_dense=int(cfg["first_k_dense_replace"]),
        D=int(cfg["hidden_size"]), H=int(cfg["num_attention_heads"]),
        q_rank=int(cfg["q_lora_rank"]), kv_rank=int(cfg["kv_lora_rank"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        v=int(cfg["v_head_dim"]), F=int(cfg["intermediate_size"]),
        F_e=int(cfg["moe_intermediate_size"]),
        E=int(cfg["published"]["n_routed_experts"]),
        held_first=int(held[0]), held=int(held[1]),
        K=int(cfg["num_experts_per_tok"]),
        shared=int(cfg["n_shared_experts"]),
        scaling=float(cfg["routed_scaling_factor"]),
        eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
        P=int(cfg["max_position_embeddings"]), V=int(cfg["vocab_size"]),
        norm_jitter=float(cfg["assumed"]["norm_scale_jitter"]))


def seed32(seed: int):
    """Any whole number a ``--seed`` can be, as the uint32 the jitted
    programs take."""
    return np.uint32(int(seed) % 2 ** 32)


# ---------------------------------------------------------------------------
# weights: one layer at a time, every value a bfloat16 number
# ---------------------------------------------------------------------------

def _matrix(key, shape, fan_in):
    """N(0, 1 / fan_in), rounded to bfloat16: what the program holds."""
    w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
    return w.astype(jnp.bfloat16)


def _scale(key, n, jitter):
    """A norm's scale: 1 + jitter * N(0, 1), a bfloat16 number."""
    return (1.0 + jitter * jax.random.normal(key, (n,), jnp.float32)
            ).astype(jnp.bfloat16)


def layer_weights(z: Dims, seed, i: int) -> Dict[str, Any]:
    """Layer ``i``'s weights as bfloat16 arrays (``i`` static; trace it
    inside a jit, ``seed`` a uint32). Matrices are N(0, 1 / fan_in): the
    inputs of every product are RMS-normalised or of unit variance, so
    router logits, attention scores and every sublayer's output stay of
    order one at any depth (the sandwich norms renormalise each sublayer
    before it is added). Norm scales are 1 + ``norm_jitter`` N(0, 1), so
    that a scale in the wrong place shows."""
    k = jax.random.split(jax.random.fold_in(
        jax.random.PRNGKey(jnp.asarray(seed, jnp.uint32)), i), 24)
    H, D, j = z.H, z.D, z.norm_jitter
    w = {
        "n_in": _scale(k[0], D, j), "n_post_attn": _scale(k[1], D, j),
        "n_pre_mlp": _scale(k[2], D, j), "n_post_mlp": _scale(k[3], D, j),
        "wq_a": _matrix(k[4], (D, z.q_rank), D),
        "q_norm": _scale(k[5], z.q_rank, j),
        "wq_b": _matrix(k[6], (z.q_rank, H * (z.nope + z.rope)), z.q_rank),
        "wkv_a": _matrix(k[7], (D, z.kv_rank + z.rope), D),
        "kv_norm": _scale(k[8], z.kv_rank, j),
        "wkv_b": _matrix(k[9], (z.kv_rank, H * (z.nope + z.v)), z.kv_rank),
        "wo": _matrix(k[10], (H * z.v, D), H * z.v),
    }
    if i < z.L_dense:
        w.update(w_gate=_matrix(k[11], (D, z.F), D),
                 w_up=_matrix(k[12], (D, z.F), D),
                 w_down=_matrix(k[13], (z.F, D), z.F))
    else:
        Fs = z.F_e * z.shared
        w.update(router=_matrix(k[14], (D, z.E), D),
                 e_gate=_matrix(k[15], (z.held, D, z.F_e), D),
                 e_up=_matrix(k[16], (z.held, D, z.F_e), D),
                 e_down=_matrix(k[17], (z.held, z.F_e, D), z.F_e),
                 s_gate=_matrix(k[18], (D, Fs), D),
                 s_up=_matrix(k[19], (D, Fs), D),
                 s_down=_matrix(k[20], (Fs, D), Fs))
    return w


def top_weights(z: Dims, seed) -> Dict[str, Any]:
    """Embedding (unit variance), final norm and the untied head."""
    k = jax.random.split(jax.random.fold_in(
        jax.random.PRNGKey(jnp.asarray(seed, jnp.uint32)), 10 ** 6), 3)
    return {"emb": jax.random.normal(k[0], (z.V, z.D), jnp.float32
                                     ).astype(jnp.bfloat16),
            "n_f": _scale(k[1], z.D, z.norm_jitter),
            "head": _matrix(k[2], (z.D, z.V), z.D)}


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


# ---------------------------------------------------------------------------
# the control's rounding
# ---------------------------------------------------------------------------

def _q(x, axis, quant):
    """``x`` rounded to int8 with one scale along ``axis`` (the largest
    magnitude maps to 127), back in float32; the identity for the
    reference."""
    if quant is None:
        return x
    if quant == "bfloat16":         # a diagnosis, not a control: see forward
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if quant != "int8":
        raise ValueError(f"unknown control precision {quant!r}")
    top = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(top > 0, top / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm(a, b, quant):
    """``a [..., K] @ b [K, N]``; the control rounds ``a`` a row and ``b``
    an output column."""
    return jnp.dot(_q(a, -1, quant), _q(b, 0, quant), precision=HIGHEST)


# ---------------------------------------------------------------------------
# the forward pass, one sequence ``x [T, D]`` at a time
# ---------------------------------------------------------------------------

def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """``x [T, ..., d]`` rotated by its row's position, rotate-half."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv              # [T, d/2]
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def _attention(x, w, z: Dims, quant):
    T = x.shape[0]
    H = z.H
    pos = jnp.arange(T)
    c_q = _rms(_mm(x, w["wq_a"], quant), w["q_norm"], z.eps)
    q = _mm(c_q, w["wq_b"], quant).reshape(T, H, z.nope + z.rope)
    kv = _mm(x, w["wkv_a"], quant)
    c_kv = _rms(kv[:, :z.kv_rank], w["kv_norm"], z.eps)
    k_rope = _rope(kv[:, z.kv_rank:], pos, z.theta)           # [T, rope]
    kvb = _mm(c_kv, w["wkv_b"], quant).reshape(T, H, z.nope + z.v)
    k = jnp.concatenate([kvb[..., :z.nope], jnp.broadcast_to(
        k_rope[:, None], (T, H, z.rope))], -1)                # [T, H, 192]
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


def _gated(x, w_gate, w_up, w_down, quant):
    return _mm(jax.nn.silu(_mm(x, w_gate, quant)) * _mm(x, w_up, quant),
               w_down, quant)


def _experts(x, w, z: Dims, quant):
    """Shared expert, plus the held experts' part of the routed sum: a
    loop over the held experts, every token through each, weighted by
    the token's gate for it (nought where it is not among its ``K``).
    Also returns the ids chosen, for the count of routing differences."""
    s = jax.nn.sigmoid(_mm(x, w["router"], quant))            # [T, E]
    top, idx = lax.top_k(s, z.K)
    gates = z.scaling * top / (jnp.sum(top, -1, keepdims=True) + 1e-20)

    def one(y, e):
        g = jnp.sum(jnp.where(idx == z.held_first + e, gates, 0.0), -1)
        return y + g[:, None] * _gated(x, w["e_gate"][e], w["e_up"][e],
                                       w["e_down"][e], quant), None

    y = _gated(x, w["s_gate"], w["s_up"], w["s_down"], quant)
    y, _ = lax.scan(one, y, jnp.arange(z.held))
    return y, idx


@functools.partial(jax.jit, static_argnames=("z", "dense", "quant"))
def block(x, w, z: Dims, dense: bool, quant: Optional[str] = None):
    """One sandwich-norm block on ``x [T, D]``; ``(y, expert ids [T, K]
    or None)``."""
    w = _f32(w)
    h = x + _rms(_attention(_rms(x, w["n_in"], z.eps), w, z, quant),
                 w["n_post_attn"], z.eps)
    u = _rms(h, w["n_pre_mlp"], z.eps)
    if dense:
        f, idx = _gated(u, w["w_gate"], w["w_up"], w["w_down"], quant), None
    else:
        f, idx = _experts(u, w, z, quant)
    return h + _rms(f, w["n_post_mlp"], z.eps), idx


@functools.partial(jax.jit, static_argnames=("z",))
def embed(top, ids, z: Dims):
    return jnp.take(top["emb"].astype(jnp.float32), ids, axis=0)


@functools.partial(jax.jit, static_argnames=("z", "quant"))
def readout(top, x, rows, z: Dims, quant: Optional[str] = None):
    top = _f32(top)
    x = _rms(jnp.take(x, rows, axis=0), top["n_f"], z.eps)
    return _mm(x, top["head"], quant)


_layer_weights = jax.jit(layer_weights, static_argnames=("z", "i"))
_top_weights = jax.jit(top_weights, static_argnames=("z",))


def pad_sequence(prompt, tokens, width: int):
    """``prompt + tokens[:-1]`` padded with zeros to ``width`` (causal
    attention keeps the padding out of every real row), and the row
    indices whose logits chose each of ``tokens``, padded to 128."""
    seq = list(prompt) + list(tokens[:-1])
    ids = np.zeros((width,), np.int32)
    ids[:len(seq)] = seq
    rows = np.zeros((-(-len(tokens) // 128) * 128,), np.int32)
    rows[:len(tokens)] = np.arange(len(prompt) - 1,
                                   len(prompt) - 1 + len(tokens))
    return ids, rows


def widths(lengths, quantum: int = ROW_QUANTUM):
    """The width each sequence of one call is padded to: the longest,
    rounded up to ``quantum`` (a power of two under it for short ones),
    or half of that for those that fit: TWO shapes a call to compile,
    whatever the lengths."""
    top = max(lengths)
    full = (-(-top // quantum) * quantum if top > quantum
            else max(32, 1 << (top - 1).bit_length()))
    return [full // 2 if n <= full // 2 else full for n in lengths]


def forward(cfg, seed: int, sequences, quant: Optional[str] = None,
            routing: Optional[list] = None):
    """``[(ids [T] int32, rows [n] int32)]`` -> float32 logits ``[n, V]``
    each, layer by layer over all sequences (one layer's weights alive
    at a time). ``routing``, a list, receives each expert layer's chosen
    ids ``[T, K]`` a sequence (numpy), for tests and for the count of
    routing differences."""
    with jax.default_matmul_precision(HIGHEST):
        z = dims(cfg)
        top = _top_weights(z, seed32(seed))
        xs = [embed(top, jnp.asarray(ids), z) for ids, _ in sequences]
        for i in range(z.L):
            w = _layer_weights(z, seed32(seed), i)
            picked = []
            for j, x in enumerate(xs):
                xs[j], idx = block(x, w, z, i < z.L_dense, quant)
                picked.append(idx)
            if routing is not None and picked[0] is not None:
                routing.append([np.asarray(p) for p in picked])
            del w
        return [np.asarray(readout(top, x, jnp.asarray(rows), z, quant))
                for x, (_, rows) in zip(xs, sequences)]


def serve_reference(cfg, seed: int, sequences, quant: Optional[str] = None):
    """For each ``(prompt, tokens)``: the float32 logits at the positions
    that produced ``tokens``, as ``[n, V]`` arrays. With ``quant`` set the
    logits are the control's."""
    lengths = [len(p) + len(t) - 1 for p, t in sequences]
    padded = [pad_sequence(p, t, w)
              for (p, t), w in zip(sequences, widths(lengths))]
    out = forward(cfg, seed, padded, quant)
    return [o[:len(t)] for o, (_, t) in zip(out, sequences)]


def train_reference(cfg, seed, batches, opt, quant=None, fault=None):
    raise NotImplementedError(
        "this configuration is served, not trained: at 16 bytes a "
        "parameter no cut inside the guide's floors fits one chip")
