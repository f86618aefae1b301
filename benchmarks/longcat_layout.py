"""The program's side of the LongCat-Flash configuration (its ``layout``
module): which model of the program runs it and how the reference's
per-layer weights (``longcat_reference.layer_weights``) lie in the
parameter tree of a ``LatentMoELM`` built from ``ShortcutMoEBlock``s. The
benchmark makes the weights and hands the program this tree; the program
hands nothing back.

What the program holds: every matrix as the bfloat16 array the reference
made (``W_kvb`` as its key and value parts, so that no compiled program
slices it); the norms' scales, the router's matrix and its selection bias
as float32 arrays of the same bfloat16 values, because the program uses
them in float32 and casts no weight inside a compiled program."""

from __future__ import annotations

import functools
from typing import Any, Dict

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks import longcat_reference as reference
from benchmarks.longcat_reference import Dims
from benchmarks.pangu_layout import MODEL_NAME, _lin, _norm
# imported here and not in build_model: a program without this block fails
# when the cell's modules are loaded, before any weight is made
from paddle_tpu.models import LatentMoELM, ShortcutMoEBlock


def build_model(z: Dims):
    """The program's model for these sizes."""
    return LatentMoELM(
        vocab=z.V, dim=z.D, num_layers=z.L, num_dense_layers=0,
        num_heads=z.H, q_rank=z.q_rank, kv_rank=z.kv_rank, nope_dim=z.nope,
        rope_dim=z.rope, v_dim=z.v, dense_hidden=z.F, expert_hidden=z.F_e,
        num_experts=z.E, top_k=z.K, experts_held=(z.held_first, z.held),
        num_shared=0, routed_scaling=z.scaling, rope_base=z.theta,
        eps=z.eps, max_len=z.P, block=ShortcutMoEBlock, scoring="softmax",
        select_bias=True, num_zero_experts=z.Z, q_scale=z.q_scale,
        kv_scale=z.kv_scale)


def loss_fn(z: Dims):
    raise NotImplementedError("this configuration is served, not trained")


def engine_facts(engine) -> Dict[str, Any]:
    """What this architecture's readers need of a built ``DecodeEngine``:
    the latent pool's dtype and its width in bytes, the values a cached
    row carries (``kv_lora_rank + qk_rope_head_dim``) and the columns it
    is stored in, the cache layers (two a double layer), the (expert
    layer, held expert) pairs a tick's ``expert_tokens`` counts over, the
    expert layers and the choices a token makes in each."""
    pool = engine.cache.pools["latent"]
    attn = engine.model.blocks[0].attn0
    experts = engine.model.blocks[0].experts
    counted = engine.model.cache_spec()["counters"]
    dtype = np.dtype(pool.dtype)
    return {"pool_dtype": str(dtype), "pool_bytes": int(dtype.itemsize),
            "latent_width": int(attn.kv_rank + attn.rope_dim),
            "latent_row_stored": int(pool.shape[-1]),
            "cache_layers": int(pool.shape[0]),
            "expert_slots": int(np.prod(counted["expert_tokens"])),
            "expert_layers": int(counted["expert_tokens"][0]),
            "top_k": int(experts.top_k)}


def _attention_tree(z: Dims, w: Dict[str, Any]) -> Dict[str, Any]:
    kvb = w["wkv_b"].reshape(z.kv_rank, z.H, z.nope + z.v)
    return {"q_a": _lin(w["wq_a"]), "q_norm": _norm(w["q_norm"]),
            "q_b": _lin(w["wq_b"]), "kv_a": _lin(w["wkv_a"]),
            "kv_norm": _norm(w["kv_norm"]),
            "kv_b_k": kvb[..., :z.nope], "kv_b_v": kvb[..., z.nope:],
            "o": _lin(w["wo"])}


def _dense_tree(w: Dict[str, Any]) -> Dict[str, Any]:
    return {"gate": _lin(w["w_gate"]), "up": _lin(w["w_up"]),
            "down": _lin(w["w_down"])}


def block_tree(z: Dims, w: Dict[str, Any]) -> Dict[str, Any]:
    """One reference double layer's weights as the program's block
    subtree."""
    return {
        "norm_attn0": _norm(w["n_a0"]), "norm_ffn0": _norm(w["n_f0"]),
        "norm_attn1": _norm(w["n_a1"]), "norm_ffn1": _norm(w["n_f1"]),
        "attn0": _attention_tree(z, w["attn0"]),
        "attn1": _attention_tree(z, w["attn1"]),
        "ffn0": _dense_tree(w["ffn0"]), "ffn1": _dense_tree(w["ffn1"]),
        "experts": {"router": w["router"].astype(jnp.float32),
                    "select_bias": w["bias"].astype(jnp.float32),
                    "gate": w["e_gate"], "up": w["e_up"],
                    "down": w["e_down"]}}


def seed_params(z: Dims, seed) -> Dict[str, Any]:
    """The seed's weights in the program's layout. Trace it inside a jit
    (``seed`` a uint32): it is all device work."""
    top = reference.top_weights(z, seed)
    model = {"emb": _lin(top["emb"]), "norm_f": _norm(top["n_f"]),
             "head": _lin(top["head"])}
    for i in range(z.L):
        model[f"block{i}"] = block_tree(z, reference.layer_weights(z, seed,
                                                                   i))
    return {MODEL_NAME: model}


@functools.partial(jax.jit, static_argnames=("z",))
def _program_params(z, seed):
    return seed_params(z, seed)


def program_params(z: Dims, seed: int) -> Dict[str, Any]:
    """The seed's weights in the program's layout, made on the device in
    one jitted call."""
    return _program_params(z, reference.seed32(seed))
