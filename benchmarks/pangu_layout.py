"""The program's side of the latent-attention expert configurations
(their ``layout`` module): which model of the program runs them and how
the reference's per-layer weights (``pangu_reference.layer_weights``) lie
in a ``LatentMoELM``'s parameter tree. The benchmark makes the weights and
hands the program this tree; the program hands nothing back.

What the program holds: every matrix as the bfloat16 array the reference
made (``W_kvb`` as its key and value parts, so that no compiled program
slices it); the norms' scales and the router's matrix as float32 arrays
of the same bfloat16 values, because the program uses them in float32 and
casts no weight inside a compiled program."""

from __future__ import annotations

import functools
from typing import Any, Dict

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks import pangu_reference as reference
from benchmarks.pangu_reference import Dims
# imported here and not in build_model: a program without this model fails
# when the cell's modules are loaded, before any weight is made
from paddle_tpu.models import LatentMoELM

MODEL_NAME = "latent_moe_lm"


def build_model(z: Dims):
    """The program's model for these sizes."""
    return LatentMoELM(
        vocab=z.V, dim=z.D, num_layers=z.L, num_dense_layers=z.L_dense,
        num_heads=z.H, q_rank=z.q_rank, kv_rank=z.kv_rank, nope_dim=z.nope,
        rope_dim=z.rope, v_dim=z.v, dense_hidden=z.F, expert_hidden=z.F_e,
        num_experts=z.E, top_k=z.K, experts_held=(z.held_first, z.held),
        num_shared=z.shared, routed_scaling=z.scaling, rope_base=z.theta,
        eps=z.eps, max_len=z.P)


def loss_fn(z: Dims):
    raise NotImplementedError("this configuration is served, not trained")


def engine_facts(engine) -> Dict[str, Any]:
    """What this architecture's readers need of a built ``DecodeEngine``:
    the latent pool's dtype and its width in bytes, the values a cached
    row carries (``kv_lora_rank + qk_rope_head_dim``) and the columns it
    is stored in, and the (expert layer, held expert) pairs a tick's
    ``expert_tokens`` counts over."""
    pool = engine.cache.pools["latent"]
    attn = engine.model.blocks[0].attn
    counted = engine.model.cache_spec().get("counters", {})
    dtype = np.dtype(pool.dtype)
    return {"pool_dtype": str(dtype), "pool_bytes": int(dtype.itemsize),
            "latent_width": int(attn.kv_rank + attn.rope_dim),
            "latent_row_stored": int(pool.shape[-1]),
            "expert_slots": int(np.prod(counted.get("expert_tokens", (0,))))}


def _norm(g):
    return {"scale": g.astype(jnp.float32)}


def _lin(w):
    return {"w": w}


def block_tree(z: Dims, w: Dict[str, Any], dense: bool) -> Dict[str, Any]:
    """One reference layer's weights as the program's block subtree."""
    kvb = w["wkv_b"].reshape(z.kv_rank, z.H, z.nope + z.v)
    blk = {
        "norm_in": _norm(w["n_in"]),
        "norm_post_attn": _norm(w["n_post_attn"]),
        "norm_pre_mlp": _norm(w["n_pre_mlp"]),
        "norm_post_mlp": _norm(w["n_post_mlp"]),
        "attn": {"q_a": _lin(w["wq_a"]), "q_norm": _norm(w["q_norm"]),
                 "q_b": _lin(w["wq_b"]), "kv_a": _lin(w["wkv_a"]),
                 "kv_norm": _norm(w["kv_norm"]),
                 "kv_b_k": kvb[..., :z.nope], "kv_b_v": kvb[..., z.nope:],
                 "o": _lin(w["wo"])},
    }
    if dense:
        blk["ffn"] = {"gate": _lin(w["w_gate"]), "up": _lin(w["w_up"]),
                      "down": _lin(w["w_down"])}
    else:
        blk["experts"] = {"router": w["router"].astype(jnp.float32),
                          "gate": w["e_gate"], "up": w["e_up"],
                          "down": w["e_down"]}
        blk["shared"] = {"gate": _lin(w["s_gate"]), "up": _lin(w["s_up"]),
                         "down": _lin(w["s_down"])}
    return blk


def seed_params(z: Dims, seed) -> Dict[str, Any]:
    """The seed's weights in the program's layout. Trace it inside a jit
    (``seed`` a uint32): it is all device work."""
    top = reference.top_weights(z, seed)
    model = {"emb": _lin(top["emb"]), "norm_f": _norm(top["n_f"]),
             "head": _lin(top["head"])}
    for i in range(z.L):
        model[f"block{i}"] = block_tree(
            z, reference.layer_weights(z, seed, i), i < z.L_dense)
    return {MODEL_NAME: model}


@functools.partial(jax.jit, static_argnames=("z",))
def _program_params(z, seed):
    return seed_params(z, seed)


def program_params(z: Dims, seed: int) -> Dict[str, Any]:
    """The seed's weights in the program's layout, made on the device in
    one jitted call."""
    return _program_params(z, reference.seed32(seed))
