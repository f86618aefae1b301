"""How the program under test lays out a ``TransformerLM``'s parameters,
against the reference's stacked layout. The benchmark makes the weights
(``reference.make_weights``) and hands the program this tree; the program
hands nothing back to the reference. Per-leaf readings of both sides are
flattened to the same keys (``wq/3``, ``wte``) before they are compared."""

from __future__ import annotations

import functools
from typing import Any, Dict

import numpy as np
import jax

from benchmarks import reference

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


@functools.partial(jax.jit, static_argnames=("z",))
def _program_params(z, seed):
    return to_program_tree(reference.make_weights(z, seed), z.L)


def program_params(z: reference.Dims, seed: int) -> Dict[str, Any]:
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
