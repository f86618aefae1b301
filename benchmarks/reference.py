"""The plain reference: a GPT-2-shaped decoder in straightforward
``jax.numpy``, float32, ``highest`` matmul precision. No kernels, no cache,
no batching tricks, and nothing imported from the program under test.

It follows the published GPT-2 block (arXiv:2304.03208 uses it unchanged)
with the departures the configuration files list: no biases on the four
attention projections, the tanh form of GELU, LayerNorm epsilon as the
configuration states it. Weights come from :func:`make_weights`, made
from the seed by the benchmark, never from the program.

Layout: per-layer leaves are stacked on a leading ``[L, ...]`` axis and the
block stack is one ``lax.scan`` with ``jax.checkpoint`` around the body, so
a training reference fits beside nothing else on one chip when it is run a
few rows at a time.

``quant="int8"`` is the CONTROL, not a reference: the next precision
below the bfloat16 the configurations state, as an int8 path of the
program would run it. Every matrix product's operands are rounded to int8
with one scale per row (activations, K and V rows) or per output column
(weights), in the forward pass and, for the linear layers, in both
backward products (the attention core's backward is straight-through).
The comparison that decides ``correct`` has to fail the control.
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


class Dims(NamedTuple):
    """What the reference needs of a configuration file, hashable."""
    L: int
    D: int
    H: int
    F: int
    P: int
    V: int
    eps: float
    act: str
    init_std: float


def dims(cfg: Dict[str, Any]) -> Dims:
    """Read a configuration file written with the source's own keys. A
    key under ``departures`` is the value as it is run and overrides the
    published one of the same name."""
    cfg = {**cfg, **cfg.get("departures", {})}
    d = int(cfg["n_embd"])
    return Dims(L=int(cfg["n_layer"]), D=d, H=int(cfg["n_head"]),
                F=int(cfg.get("n_inner") or 4 * d),
                P=int(cfg["n_positions"]), V=int(cfg["vocab_size"]),
                eps=float(cfg["layer_norm_epsilon"]),
                act=str(cfg["activation_function"]),
                init_std=float(cfg.get("initializer_range", 0.02)))


def seed32(seed: int):
    """Any whole number a ``--seed`` can be, as the uint32 the jitted
    programs take (a Python int over 2**31 would not pass as int32)."""
    return np.uint32(int(seed) % 2 ** 32)


def seed_key(seed):
    """A PRNG key from any whole number up to 2**32 (a Python int or a
    traced uint32)."""
    return jax.random.PRNGKey(jnp.asarray(seed, jnp.uint32))


def make_weights(z: Dims, seed) -> Dict[str, Any]:
    """GPT-2 initialisation from the seed, float32: N(0, initializer_range)
    for every matrix and both embeddings, the two residual output
    projections scaled by 1/sqrt(2 L), zero biases, unit LayerNorm scales.
    Trace it inside one jit: it is all device work."""
    L, D, F, P, V, std = z.L, z.D, z.F, z.P, z.V, z.init_std
    out_std = std / math.sqrt(2 * L)
    k = jax.random.split(seed_key(seed), 8)
    n = lambda key, shape, s: s * jax.random.normal(key, shape, jnp.float32)
    ones = lambda *s: jnp.ones(s, jnp.float32)
    zeros = lambda *s: jnp.zeros(s, jnp.float32)
    return {
        "wte": n(k[0], (V, D), std), "wpe": n(k[1], (P, D), std),
        "ln_f_g": ones(D), "ln_f_b": zeros(D),
        "blocks": {
            "ln1_g": ones(L, D), "ln1_b": zeros(L, D),
            "wq": n(k[2], (L, D, D), std), "wk": n(k[3], (L, D, D), std),
            "wv": n(k[4], (L, D, D), std), "wo": n(k[5], (L, D, D), out_std),
            "ln2_g": ones(L, D), "ln2_b": zeros(L, D),
            "w1": n(k[6], (L, D, F), std), "b1": zeros(L, F),
            "w2": n(k[7], (L, F, D), out_std), "b2": zeros(L, D),
        },
    }


@functools.partial(jax.jit, static_argnames=("z",))
def _make_weights(z, seed):
    return make_weights(z, seed)


def init_weights(z: Dims, seed: int) -> Dict[str, Any]:
    """:func:`make_weights` in one jitted call on the default device."""
    return _make_weights(z, seed32(seed))


# ---------------------------------------------------------------------------
# the control's rounding
# ---------------------------------------------------------------------------

def _round(x, axis):
    """``x`` rounded to int8 with one scale along ``axis`` (the largest
    magnitude maps to 127), back in float32."""
    top = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(top > 0, top / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _q(x, axis, quant):
    """The control's rounding of a forward operand, straight-through for
    the gradient; the identity for the reference."""
    if quant is None:
        return x
    if quant != "int8":
        raise ValueError(f"unknown control precision {quant!r}")
    return x + lax.stop_gradient(_round(x, axis) - x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _mm_lowp(a, b, quant):
    return jnp.dot(_q(a, -1, quant), _q(b, 0, quant), precision=HIGHEST)


def _mm_lowp_fwd(a, b, quant):
    qa, qb = _q(a, -1, quant), _q(b, 0, quant)
    return jnp.dot(qa, qb, precision=HIGHEST), (qa, qb)


def _mm_lowp_bwd(quant, res, g):
    """Both backward products with the incoming gradient rounded too."""
    qa, qb = res
    qg = _round(g, -1)
    da = jnp.dot(qg, qb.T, precision=HIGHEST)
    db = jnp.tensordot(qa, qg, axes=(tuple(range(qa.ndim - 1)),) * 2,
                       precision=HIGHEST)
    return da, db


_mm_lowp.defvjp(_mm_lowp_fwd, _mm_lowp_bwd)


def _mm(a, b, quant):
    """``a [..., K] @ b [K, N]``. The control rounds ``a`` per row and
    ``b`` per output column and, in both backward products, the incoming
    gradient per row."""
    if quant is not None:
        return _mm_lowp(a, b, quant)
    return jnp.dot(a, b, precision=HIGHEST)


def _layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * g + b


def _gelu(x, form):
    if form == "gelu_tanh":
        c = math.sqrt(2.0 / math.pi)
        return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))
    return 0.5 * x * (1.0 + lax.erf(x / math.sqrt(2.0)))


def _block(x, p, H, eps, act, quant):
    """One pre-LN block on ``x [B, T, D]``."""
    B, T, D = x.shape
    dh = D // H
    h = _layer_norm(x, p["ln1_g"], p["ln1_b"], eps)
    split = lambda y: y.reshape(B, T, H, dh).transpose(0, 2, 1, 3)
    q, k, v = (split(_mm(h, p[n], quant)) for n in ("wq", "wk", "wv"))
    q, k, v = _q(q, -1, quant), _q(k, -1, quant), _q(v, -1, quant)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=HIGHEST)
    s = s / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    w = _q(jax.nn.softmax(s, axis=-1), -1, quant)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", w, v, precision=HIGHEST)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, T, D)
    x = x + _mm(ctx, p["wo"], quant)
    z = _layer_norm(x, p["ln2_g"], p["ln2_b"], eps)
    y = _gelu(_mm(z, p["w1"], quant) + p["b1"], act)
    return x + _mm(y, p["w2"], quant) + p["b2"]


def hidden(w, ids, z: Dims, quant: Optional[str] = None):
    """``ids [B, T] -> final-LayerNorm activations [B, T, D]``."""
    H, eps, act = z.H, z.eps, z.act
    T = ids.shape[1]
    x = jnp.take(w["wte"], ids, axis=0) + w["wpe"][:T][None]

    @jax.checkpoint
    def body(x, p):
        return _block(x, p, H, eps, act, quant), None

    x, _ = lax.scan(body, x, w["blocks"])
    return _layer_norm(x, w["ln_f_g"], w["ln_f_b"], eps)


def logits_at(w, ids, rows, z: Dims, quant: Optional[str] = None):
    """Logits ``[len(rows), V]`` of one sequence ``ids [T]`` at the
    positions ``rows`` (tied readout)."""
    x = hidden(w, ids[None], z, quant)[0]
    return _mm(jnp.take(x, rows, axis=0), w["wte"].T, quant)


def loss(w, x_ids, y_ids, z: Dims, quant: Optional[str] = None):
    """Sum over rows and positions of the next-token negative
    log-likelihood (the caller divides by the token count)."""
    h = hidden(w, x_ids, z, quant)
    logits = _mm(h, w["wte"].T, quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, y_ids[..., None], axis=-1).sum()


@functools.partial(jax.jit, static_argnames=("z", "quant"))
def loss_and_grads(w, x_ids, y_ids, z: Dims, quant: Optional[str] = None):
    """Mean loss and its gradient over a batch, one row at a time
    (``lax.map`` over rows, each row rematerialised in the backward pass)
    so that one row's activations are all that is alive beside the
    weights, the gradient and Adam's moments."""
    def mean_loss(w):
        row = jax.checkpoint(
            lambda xy: loss(w, xy[0][None], xy[1][None], z, quant))
        return lax.map(row, (x_ids, y_ids)).sum() / x_ids.size
    return jax.value_and_grad(mean_loss)(w)


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps"),
                   donate_argnums=(0, 2, 3))
def adam_step(w, grads, m, v, t, lr, b1, b2, eps):
    """Adam as published (Kingma and Ba), bias-corrected, step ``t`` from 1."""
    tm = jax.tree_util.tree_map
    m = tm(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = tm(lambda v, g: b2 * v + (1 - b2) * jnp.square(g), v, grads)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    w = tm(lambda w, m, v: w - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps),
           w, m, v)
    return w, m, v


@jax.jit
def leaf_norms(tree):
    """L2 norm of every program-sized leaf: stacked block leaves give one
    norm per layer."""
    def norm(path, x):
        in_blocks = path and getattr(path[0], "key", None) == "blocks"
        axes = tuple(range(1, x.ndim)) if in_blocks else None
        return jnp.sqrt(jnp.sum(jnp.square(x), axis=axes))
    return jax.tree_util.tree_map_with_path(norm, tree)


def train_reference(cfg, seed: int, batches, opt: Dict[str, float],
                    quant: Optional[str] = None,
                    fault: Optional[str] = None) -> Dict[str, Any]:
    """Follow the first ``len(batches)`` optimizer steps from the seed's
    weights. Returns each step's loss, the per-leaf norms of the first
    gradient and the per-leaf norms of the parameters' change after the
    last step. Planted faults, for the limits and the tests:
    ``fault="half_batch"`` leaves out the second half of every batch and
    takes the mean over the rest; ``fault="state_unchanged"`` takes every
    step and returns the state as it was."""
    with jax.default_matmul_precision(HIGHEST):
        z = dims(cfg)
        w = init_weights(z, seed)
        m = jax.tree_util.tree_map(jnp.zeros_like, w)
        v = jax.tree_util.tree_map(jnp.zeros_like, w)
        losses, grad_norms = [], None
        for t, (x_ids, y_ids) in enumerate(batches, start=1):
            x_ids, y_ids = jnp.asarray(x_ids), jnp.asarray(y_ids)
            if fault == "half_batch":
                half = max(1, x_ids.shape[0] // 2)
                x_ids, y_ids = x_ids[:half], y_ids[:half]
            l, g = loss_and_grads(w, x_ids, y_ids, z, quant)
            losses.append(float(l))
            if grad_norms is None:
                grad_norms = jax.device_get(leaf_norms(g))
            if fault != "state_unchanged":
                w, m, v = adam_step(w, g, m, v, t, float(opt["lr"]),
                                    float(opt["b1"]), float(opt["b2"]),
                                    float(opt["eps"]))
            del g
        w0 = init_weights(z, seed)
        change = jax.device_get(leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, w, w0)))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


def serve_reference(cfg, seed: int, sequences, quant: Optional[str] = None):
    """For each ``(prompt, tokens)``: the float32 logits at the positions
    that produced ``tokens``, as ``[n, V]`` arrays. With ``quant`` set the
    logits are the control's."""
    with jax.default_matmul_precision(HIGHEST):
        z = dims(cfg)
        w = init_weights(z, seed)
        fn = jax.jit(functools.partial(logits_at, z=z, quant=quant))
        out = []
        for prompt, tokens in sequences:
            ids, rows = pad_sequence(prompt, tokens, z.P)
            out.append(jax.device_get(fn(w, jnp.asarray(ids),
                                         jnp.asarray(rows)))[:len(tokens)])
    return out


def pad_sequence(prompt, tokens, width: int, quantum: int = 256):
    """``prompt + tokens[:-1]`` padded with zeros to a multiple of
    ``quantum`` (causal attention keeps the padding out of every real
    row; a few widths compile instead of one per length), and the row
    indices whose logits chose each of ``tokens``, padded to 128."""
    seq = list(prompt) + list(tokens[:-1])
    T = min(width, -(-len(seq) // quantum) * quantum)
    ids = np.zeros((T,), np.int32)
    ids[:len(seq)] = seq
    n_rows = -(-len(tokens) // 128) * 128
    rows = np.zeros((n_rows,), np.int32)
    rows[:len(tokens)] = np.arange(len(prompt) - 1,
                                   len(prompt) - 1 + len(tokens))
    return ids, rows
