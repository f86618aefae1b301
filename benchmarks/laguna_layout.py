"""The program's side of the Laguna-S-2.1 configuration (its ``layout``
module): which model of the program runs it and how the reference's
per-layer weights (``laguna_reference.layer_weights``) lie in a
``WindowMoELM``'s parameter tree. The benchmark makes the weights and hands
the program this tree; the program hands nothing back.

What the program holds: every matrix as the bfloat16 array the reference
made; the norms' scales and the router's matrix as float32 arrays of the
same bfloat16 values, because the program uses them in float32 and casts
no weight inside a compiled program."""

from __future__ import annotations

import functools
from typing import Any, Dict

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks import laguna_reference as reference
from benchmarks.laguna_reference import Dims, Rotary
from benchmarks.pangu_layout import _lin, _norm
# imported here and not in build_model: a program without this model fails
# when the cell's modules are loaded, before any weight is made
from paddle_tpu.models import WindowMoELM

MODEL_NAME = "window_moe_lm"


def _rotary_args(r: Rotary) -> Dict[str, Any]:
    args: Dict[str, Any] = {"rope_base": r.theta, "rope_dim": r.dim}
    if r.factor != 1.0:
        args["yarn"] = {"factor": r.factor, "original_len": r.original,
                        "beta_fast": r.beta_fast, "beta_slow": r.beta_slow,
                        "attention_factor": r.attention_factor}
    return args


def build_model(z: Dims):
    """The program's model for these sizes."""
    return WindowMoELM(
        vocab=z.V, dim=z.D, layer_windows=z.windows, layer_heads=z.heads,
        num_kv_heads=z.H_kv, head_dim=z.hd,
        rotary={"full": _rotary_args(z.rope_full),
                "window": _rotary_args(z.rope_window)},
        dense_hidden=z.F, expert_hidden=z.F_e, shared_hidden=z.F_s,
        num_experts=z.E, top_k=z.K, experts_held=(z.held_first, z.held),
        dense_layers=[i for i, d in enumerate(z.dense) if d],
        routed_scaling=z.scaling, eps=z.eps, max_len=z.P)


def loss_fn(z: Dims):
    raise NotImplementedError("this configuration is served, not trained")


def engine_facts(engine) -> Dict[str, Any]:
    """What this architecture's readers need of a built ``DecodeEngine``:
    the pools' dtype and its width in bytes; each pool group's layers,
    window, block bytes and blocks (``PagedKVCache.group_facts``, with the
    blocks in use when the window closed); the query heads of each layer
    kind, the KV heads and the head size; the (expert layer, held expert)
    pairs a tick's ``expert_tokens`` counts over."""
    cache, blocks = engine.cache, engine.model.blocks
    dtype = np.dtype(next(iter(cache.pools.values())).dtype)
    counted = engine.model.cache_spec().get("counters", {})
    return {"pool_dtype": str(dtype), "pool_bytes": int(dtype.itemsize),
            "block_size": int(cache.block_size),
            "pool_groups": cache.group_facts(),
            "query_heads": {b.kind: int(b.attn.num_heads) for b in blocks},
            "kv_heads": int(blocks[0].attn.num_kv_heads),
            "head_dim": int(blocks[0].attn.head_dim),
            "expert_slots": int(np.prod(counted.get("expert_tokens", (0,))))}


def block_tree(z: Dims, i: int, w: Dict[str, Any]) -> Dict[str, Any]:
    """Reference layer ``i``'s weights as the program's block subtree."""
    tree = {"norm_attn": _norm(w["n_attn"]), "norm_mlp": _norm(w["n_mlp"]),
            # q, k and v held [heads x head size, hidden]: the program's
            # products sum over the minor axis (GroupedQueryAttention._heads)
            "attn": {"wq": w["wq"].T, "wk": w["wk"].T, "wv": w["wv"].T,
                     "gate": _lin(w["wg"]), "o": _lin(w["wo"])}}
    if z.dense[i]:
        tree["ffn"] = {"gate": _lin(w["w_gate"]), "up": _lin(w["w_up"]),
                       "down": _lin(w["w_down"])}
    else:
        tree["shared"] = {"gate": _lin(w["s_gate"]), "up": _lin(w["s_up"]),
                          "down": _lin(w["s_down"])}
        tree["experts"] = {"router": w["router"].astype(jnp.float32),
                           "gate": w["e_gate"], "up": w["e_up"],
                           "down": w["e_down"]}
    return tree


def seed_params(z: Dims, seed) -> Dict[str, Any]:
    """The seed's weights in the program's layout. Trace it inside a jit
    (``seed`` a uint32): it is all device work."""
    top = reference.top_weights(z, seed)
    model = {"emb": _lin(top["emb"]), "norm_f": _norm(top["n_f"]),
             "head": _lin(top["head"])}
    for i in range(z.L):
        model[f"block{i}"] = block_tree(z, i,
                                        reference.layer_weights(z, seed, i))
    return {MODEL_NAME: model}


@functools.partial(jax.jit, static_argnames=("z",))
def _program_params(z, seed):
    return seed_params(z, seed)


def program_params(z: Dims, seed: int) -> Dict[str, Any]:
    """The seed's weights in the program's layout, made on the device in
    one jitted call."""
    return _program_params(z, reference.seed32(seed))
