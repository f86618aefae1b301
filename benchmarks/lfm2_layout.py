"""The program's side of the LFM2-24B-A2B configuration (its ``layout``
module): which model of the program runs it and how the reference's
per-layer weights (``lfm2_reference.layer_weights``) lie in a
``ConvMoELM``'s parameter tree. The benchmark makes the weights and hands
the program this tree; the program hands nothing back.

What the program holds: every matrix as the bfloat16 array the reference
made (q, k and v transposed, as ``GroupedQueryAttention`` holds them); the
norms' scales, the convolution's taps (``[3, D]``: a tap's row
contiguous), the router's matrix and the expert bias as float32 arrays of
the same bfloat16 values, because the program uses them in float32 and
casts no weight inside a compiled program."""

from __future__ import annotations

import functools
from typing import Any, Dict

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks import lfm2_reference as reference
from benchmarks.lfm2_reference import Dims
from benchmarks.pangu_layout import _lin, _norm
# imported here and not in build_model: a program without this model fails
# when the cell's modules are loaded, before any weight is made
from paddle_tpu.models import ConvMoELM
from paddle_tpu.models.conv_moe import KINDS

MODEL_NAME = "conv_moe_lm"
# a block's kind back to the source's layer type
SOURCE_TYPES = {kind: t for t, kind in KINDS.items()}


def build_model(z: Dims):
    """The program's model for these sizes."""
    return ConvMoELM(
        vocab=z.V, dim=z.D, layer_types=[SOURCE_TYPES[k] for k in z.kinds],
        num_heads=z.H, num_kv_heads=z.H_kv, head_dim=z.hd,
        dense_hidden=z.F, expert_hidden=z.F_e, num_experts=z.E, top_k=z.K,
        experts_held=(z.held_first, z.held), dense_layers=sum(z.dense),
        conv_kernel=z.conv_k, rope_base=z.theta, routed_scaling=z.scaling,
        router_eps=z.router_eps, eps=z.eps, max_len=z.P)


def loss_fn(z: Dims):
    raise NotImplementedError("this configuration is served, not trained")


def engine_facts(engine) -> Dict[str, Any]:
    """What this architecture's readers need of a built ``DecodeEngine``:
    the pools' dtype and its width in bytes; the paged group's layers,
    block bytes and blocks (``PagedKVCache.group_facts``: the ``full``
    group alone) and, under a key of its own, the convolutions' state
    group and its bytes (``state_facts``); the attention layers' query
    heads, the KV heads and the head size; the (expert layer, expert)
    pairs a tick's ``expert_tokens`` counts over."""
    cache, blocks = engine.cache, engine.model.blocks
    dtype = np.dtype(cache.pools["full/k"].dtype)
    counted = engine.model.cache_spec().get("counters", {})
    attn = next(b.attn for b in blocks if b.kind == "full")
    return {"pool_dtype": str(dtype), "pool_bytes": int(dtype.itemsize),
            "block_size": int(cache.block_size),
            "pool_groups": cache.group_facts(),
            "state_groups": cache.state_facts(),
            "query_heads": {"full": int(attn.num_heads)},
            "kv_heads": int(attn.num_kv_heads),
            "head_dim": int(attn.head_dim),
            "expert_slots": int(np.prod(counted.get("expert_tokens", (0,))))}


def block_tree(z: Dims, i: int, w: Dict[str, Any]) -> Dict[str, Any]:
    """Reference layer ``i``'s weights as the program's block subtree."""
    tree = {"norm_mix": _norm(w["n_mix"]), "norm_ffn": _norm(w["n_ffn"])}
    if z.kinds[i] == "conv":
        tree["conv"] = {"in_proj": _lin(w["w_in"]),
                        "conv": w["conv"].T.astype(jnp.float32),
                        "out_proj": _lin(w["w_out"])}
    else:
        # q, k and v held [heads x head size, hidden]: the program's
        # products sum over the minor axis (GroupedQueryAttention._heads)
        tree["attn"] = {"wq": w["wq"].T, "wk": w["wk"].T, "wv": w["wv"].T,
                        "o": _lin(w["wo"]), "q_norm": _norm(w["qn"]),
                        "k_norm": _norm(w["kn"])}
    if z.dense[i]:
        tree["ffn"] = {"gate": _lin(w["w_gate"]), "up": _lin(w["w_up"]),
                       "down": _lin(w["w_down"])}
    else:
        tree["experts"] = {"router": w["router"].astype(jnp.float32),
                           "select_bias": w["bias"].astype(jnp.float32),
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
