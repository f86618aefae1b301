"""The program's side of the GPT-2-shaped configurations (their ``layout``
module): which model of the program runs them, its training loss, and how
it lays out a ``TransformerLM``'s parameters against the reference's
stacked layout. The benchmark makes the weights
(``reference.make_weights``) and hands the program this tree; the program
hands nothing back to the reference. Per-leaf readings of both sides are
flattened to the same keys (``wq/3``, ``wte``) before they are compared."""

from __future__ import annotations

import functools
from typing import Any, Dict

import numpy as np
import jax

from benchmarks import reference
from benchmarks.reference import Dims

# program block leaf -> reference stacked leaf
BLOCK_LEAVES = {
    ("attn", "wq"): "wq", ("attn", "wk"): "wk", ("attn", "wv"): "wv",
    ("attn", "wo"): "wo", ("ffn1", "w"): "w1", ("ffn1", "b"): "b1",
    ("ffn2", "w"): "w2", ("ffn2", "b"): "b2",
    ("ln1", "scale"): "ln1_g", ("ln1", "bias"): "ln1_b",
    ("ln2", "scale"): "ln2_g", ("ln2", "bias"): "ln2_b",
}
TOP_LEAVES = {("emb", "w"): "wte", ("pos", "w"): "wpe",
              ("ln_f", "scale"): "ln_f_g", ("ln_f", "bias"): "ln_f_b"}
MODEL_NAME = "transformer_lm"


def build_model(z: Dims):
    """The program's model for these sizes."""
    from paddle_tpu.models import TransformerLM
    return TransformerLM(vocab=z.V, dim=z.D, num_layers=z.L,
                         num_heads=z.H, ffn_hidden=z.F, max_len=z.P,
                         use_flash=True)


def loss_fn(z: Dims):
    """The program's training loss on the model's output and a batch
    ``{"x", "y"}``: mean next-token cross-entropy over every position."""
    from paddle_tpu.nn import costs as nn_costs
    return lambda out, b: nn_costs.softmax_cross_entropy(
        out.reshape(-1, z.V), b["y"].reshape(-1))


def engine_facts(engine) -> Dict[str, Any]:
    """What this architecture's readers need of a built ``DecodeEngine``:
    the K / V pools' dtype and its width in bytes."""
    k = engine.cache.k
    dtype = np.dtype((k[0] if isinstance(k, tuple) else k).dtype)
    return {"pool_dtype": str(dtype), "pool_bytes": int(dtype.itemsize)}


def to_program_tree(w: Dict[str, Any], n_layers: int) -> Dict[str, Any]:
    """Reference-layout weights as the program's ``params`` tree."""
    model: Dict[str, Any] = {}
    for (mod, leaf), ref in TOP_LEAVES.items():
        model.setdefault(mod, {})[leaf] = w[ref]
    for i in range(n_layers):
        blk: Dict[str, Any] = {}
        for (mod, leaf), ref in BLOCK_LEAVES.items():
            blk.setdefault(mod, {})[leaf] = w["blocks"][ref][i]
        model[f"block{i}"] = blk
    return {MODEL_NAME: model}


def seed_params(z: Dims, seed) -> Dict[str, Any]:
    """The seed's weights in the program's layout. Trace it inside a jit
    (``seed`` a uint32): it is all device work."""
    return to_program_tree(reference.make_weights(z, seed), z.L)


@functools.partial(jax.jit, static_argnames=("z",))
def _program_params(z, seed):
    return seed_params(z, seed)


def program_params(z: Dims, seed: int) -> Dict[str, Any]:
    """The seed's weights in the program's layout, made on the device in
    one jitted call."""
    return _program_params(z, reference.seed32(seed))


def flatten_reference(tree: Dict[str, Any]) -> Dict[str, float]:
    """Per-leaf readings of the reference (stacked leaves hold one per
    layer) under the common keys."""
    out = {k: float(v) for k, v in tree.items() if k != "blocks"}
    for name, per_layer in tree["blocks"].items():
        for i, v in enumerate(np.asarray(per_layer)):
            out[f"{name}/{i}"] = float(v)
    return out


def flatten_program(tree: Dict[str, Any]) -> Dict[str, float]:
    """Per-leaf readings of the program's ``params``-shaped tree under
    the common keys."""
    model = tree[MODEL_NAME] if MODEL_NAME in tree else tree
    out: Dict[str, float] = {}
    for (mod, leaf), ref in TOP_LEAVES.items():
        out[ref] = float(model[mod][leaf])
    i = 0
    while f"block{i}" in model:
        for (mod, leaf), ref in BLOCK_LEAVES.items():
            out[f"{ref}/{i}"] = float(model[f"block{i}"][mod][leaf])
        i += 1
    return out
