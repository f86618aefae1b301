"""Analytic multi-chip scaling projection from the sharded step's HLO.

VERDICT r3 item 6 built the method; VERDICT r4 item 6 asked for the
POSITIVE tp/pp story (r4's only tp datapoint was a config tp should lose
at). The projection:

1. For each workload config and device count n, compile the REAL sharded
   training step on a forced n-device virtual CPU platform and parse the
   optimized (post-SPMD) HLO for the collectives XLA actually inserted
   (all-reduce / all-gather / reduce-scatter / all-to-all /
   collective-permute) with their buffer sizes. Transformer workloads
   lower ABSTRACTLY (ShapeDtypeStruct args carrying NamedShardings — no
   host buffers), so big-model big-mesh compiles fit in host RAM.
2. Convert buffers to per-device wire bytes with the standard ring-algorithm
   factors over each op's replica group (all-reduce 2B(n-1)/n,
   gather/all-to-all B(n-1)/n, reduce-scatter B(n-1) of the shard,
   permute B). Pipeline ppermutes inside the wavefront loop are scaled by
   the tick count (static-op parse x dynamic executions).
3. Combine with public per-chip ICI bandwidth and the measured single-chip
   step time into projected scaling efficiency, with no comm/compute
   overlap (pessimistic) and perfect overlap (optimistic bound). Pipeline
   workloads also charge the GPipe bubble (S-1)/M as a compute overhead
   factor, so their efficiency is vs ideal linear scaling, not vs an
   already-bubbled baseline.

Workload matrix (the tp/pp story):
  - d512 tp=4            — the r4 NEGATIVE result, kept for contrast
  - d512 tp=4 + sp       — EXPLICIT Megatron sequence-parallel residuals
    (parallel/megatron.py): AG+RS at all-reduce-equal wire, loss inside
    the shard_map so nothing [*,vocab]-shaped is gathered
  - d1024 dp x pp=8      — GPipe block pipeline via make_pipeline_loss
    (scalar-psum loss form; ppermute hops), M=32
  - d2048 tp=4 + sp      — the dim where tp=4 SHOULD win (tp comm scales
    with d, compute with d^2)
plus analytic dp-only baselines per model, so the final ``recommended``
section names the best config per (model, n) against dp, not in a vacuum.

Cross-checks: (a) at n=8 the parsed resnet all-reduce buffer bytes must
match the analytic f32 gradient size within 10%; (b) a MEASURED virtual-
CPU-mesh transformer dp point at n=8 anchors the transformer projection to
an executed (not just compiled) sharded step.

Output: ``SCALING_r05.json`` at the repo root (run from repo root:
``python experiments/scaling_projection.py``).

Reference anchor: the 3.85x-at-4-GPUs table,
``/root/reference/benchmark/README.md:70-93``.
"""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ISSUE 6: the collective-parsing machinery this experiment pioneered is
# now the library's (paddle_tpu/obs/hloprof.py — the same regexes, shape
# rules, and ring factors, verbatim). tests/test_hloprof.py pins the
# aggregate's variadic/iota-group/async-start/ring-factor behaviors and
# its totals against the structured inventory, so the committed
# SCALING_* numbers cannot drift. Loaded by FILE PATH, not through the
# paddle_tpu package: hloprof.py is deliberately stdlib-only, and this
# driver does all jax work in env-controlled subprocesses — importing
# the package here would eagerly initialize jax in the parent.
import importlib.util as _ilu  # noqa: E402

_spec = _ilu.spec_from_file_location(
    "_hloprof", os.path.join(REPO, "paddle_tpu", "obs", "hloprof.py"))
_hloprof = _ilu.module_from_spec(_spec)
sys.modules["_hloprof"] = _hloprof      # dataclasses resolve via sys.modules
_spec.loader.exec_module(_hloprof)
parse_collectives = _hloprof.parse_collectives

# Public per-chip interconnect specs (cloud.google.com/tpu/docs spec
# sheets): v5e ICI 1,600 Gbit/s per chip aggregate -> 200 GB/s; one-way
# usable per direction ~100 GB/s. DCN (inter-slice) ~ 25 GB/s per host.
ICI_BYTES_PER_S = 100e9          # one-way per chip, v5e
DCN_BYTES_PER_S = 25e9 / 8      # per chip when 8 chips share a host NIC
ICI_POD_LIMIT = 256              # v5e pod: 256 chips on one ICI fabric

# Measured single-chip step times (PERF.md (older installation) protocol; this
# round's numbers) and the transformer model zoo. t_comp is the IDEAL
# per-chip step time at that parallelism (single-chip time / model-split
# factor); pipeline bubble is charged separately via overhead_factor.
WORKLOADS = {
    "resnet50_dp": {
        "t_comp_ms": 47.1,           # measured (PERF.md r5 stem fix, bs128)
        "mode": "resnet", "all_ar_is_grad": True,
        "note": "ResNet-50 bs128/chip bf16, pure data parallel",
    },
    "transformer_dp_tp": {
        "t_comp_ms": None,           # filled from MEASURED_MS at load
        "mode": "tp", "d": 512, "L": 6, "H": 4, "ffn": 2048,
        "tp": 4, "sp": False, "bs_group": 8,
        "note": "TransformerLM d512 L6 seq2048, dp x tp=4 (the r4 NEGATIVE "
                "kept for contrast: at d512 the Megatron activation "
                "all-reduces make tp=4 ICI-heavy; see the _sp and d2048 "
                "rows for the configs that fix it)",
    },
    "transformer_dp_tp_sp": {
        "t_comp_ms": None,
        "mode": "tp", "d": 512, "L": 6, "H": 4, "ffn": 2048,
        "tp": 4, "sp": True, "bs_group": 8, "all_ar_is_grad": True,
        "note": "d512 tp=4 with EXPLICIT Megatron sequence-parallel "
                "residuals (parallel.make_megatron_sp_lm_apply, bf16 "
                "comm compression): AG+RS pairs replace the all-reduces, "
                "wire halved by comm_dtype=bf16, residuals/"
                "LayerNorms/activation memory shard T/tp per device; loss "
                "computed inside the shard_map so nothing [*,vocab]-"
                "shaped is ever gathered",
    },
    "transformer_d1024_dp_pp": {
        "t_comp_ms": None,
        "mode": "pp", "d": 1024, "L": 8, "H": 8, "ffn": 4096,
        "pp": 8, "microbatches": 32, "mb_rows_group": 4,
        "all_ar_is_grad": True,
        "note": "TransformerLM d1024 L8 seq2048, dp x GPipe pipe=8 (one "
                "block per stage, M=32 microbatches of 4 rows per dp "
                "group) via parallel.make_pipeline_loss — loss closes on "
                "the last stage (scalar psum; the naive replicated-output "
                "form pays a 1.07 GB/step pipe-axis broadcast, measured "
                "r5); activations hop via ppermute; efficiency charges "
                "the (S-1)/M bubble as compute overhead",
    },
    "transformer_d2048_dp_tp_sp": {
        "t_comp_ms": None,
        "mode": "tp", "d": 2048, "L": 8, "H": 16, "ffn": 8192,
        "tp": 4, "sp": True, "bs_group": 8, "all_ar_is_grad": True,
        "note": "TransformerLM d2048 L8 seq2048, dp x tp=4 + seq-parallel "
                "residuals (bf16 comm compression) — the dim where tp=4 "
                "should win: tp wire scales with d, compute with d^2",
    },
}

# Measured single-chip ms/step anchors (real v5e chip, interleaved
# differential; PERF.md (older installation) "Round 5", dh=128 geometry). d512 and
# d1024 are at the bench shapes; d2048's bs8 group batch is anchored to
# the measured bs4 step (see _fill_t_comp).
MEASURED_MS = {
    "d512_bs8": 51.3,            # H4, 40.4% MFU
    "d1024_bs16": 339.1,         # H8, 44.2% MFU
    "d2048_bs4": 247.3,          # H16, 50.6% MFU (bs8 full-step OOMs the
                                 # 16 GB chip with adam states resident —
                                 # the tp group's whole point is that 4
                                 # chips share this model)
}

# per-model totals for the analytic dp-only baseline rows (params from
# model.init leaf sizes: blocks 12*d^2*L + tied emb V*d + pos T*d)
PARAM_COUNTS = {
    "d512": 12 * 512 * 512 * 6 + 32000 * 512 + 2048 * 512,
    "d1024": 12 * 1024 * 1024 * 8 + 32000 * 1024 + 2048 * 1024,
    "d2048": 12 * 2048 * 2048 * 8 + 32000 * 2048 + 2048 * 2048,
}


def _fill_t_comp():
    w = WORKLOADS
    w["transformer_dp_tp"]["t_comp_ms"] = \
        round(MEASURED_MS["d512_bs8"] / 4, 2)
    w["transformer_dp_tp_sp"]["t_comp_ms"] = \
        round(MEASURED_MS["d512_bs8"] / 4, 2)
    # one full pipeline group of 8 chips processes 8x the single-chip
    # batch: ideal per-chip time == the single-chip bs16 step time
    w["transformer_d1024_dp_pp"]["t_comp_ms"] = MEASURED_MS["d1024_bs16"]
    S = w["transformer_d1024_dp_pp"]["pp"]
    M = w["transformer_d1024_dp_pp"]["microbatches"]
    w["transformer_d1024_dp_pp"]["overhead_factor"] = (S - 1) / M
    # bs8 anchor = 2x the measured bs4 step: compute-bound at 50.6% MFU,
    # so batch scaling is ~linear (sub-linearity would only raise MFU and
    # efficiency; recorded as t_comp_basis on the workload)
    w["transformer_d2048_dp_tp_sp"]["t_comp_ms"] = \
        round(2 * MEASURED_MS["d2048_bs4"] / 4, 2)
    w["transformer_d2048_dp_tp_sp"]["t_comp_basis"] = \
        "2x measured bs4 single-chip step (247.3 ms, 50.6% MFU)"


_RESNET_CODE = """
import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
import paddle_tpu as pt
from paddle_tpu import optim
from paddle_tpu.nn import costs
from paddle_tpu.train import Trainer

import json
n = %(n)d
devices = jax.devices()[:n]
# small image: conv activations shrink (fast CPU compile) while the
# gradient all-reduce — the thing we are counting — is unchanged
from paddle_tpu.models import resnet50
mesh = pt.make_mesh({"data": n}, devices=devices)
trainer = Trainer(model=resnet50(num_classes=1000),
                  loss_fn=lambda out, b: costs.softmax_cross_entropy(
                      out, b["label"]),
                  optimizer=optim.momentum(0.1, 0.9), mesh=mesh)
rng = np.random.RandomState(0)
batch = {"x": rng.normal(size=(2 * n, 64, 64, 3)).astype(np.float32),
         "label": rng.randint(0, 1000, size=2 * n).astype(np.int32)}
trainer.init(jax.random.PRNGKey(0), batch)
trainer._build_train_step()
ts = trainer.train_state
sharded = trainer._shard(batch)
lowered = trainer._train_step.lower(ts.params, ts.state, ts.opt_state,
                                    ts.step, sharded,
                                    jax.random.PRNGKey(1))
print("=====HLO=====")
print(lowered.compile().as_text())
"""

_TRANSFORMER_CODE = """
import json, sys
import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
import paddle_tpu as pt
from paddle_tpu import optim, parallel
from paddle_tpu.nn import costs
from paddle_tpu.models import TransformerLM
from paddle_tpu.optim.optimizers import apply_updates

cfg = json.loads(%(cfg)r)
n = %(n)d
devices = jax.devices()[:n]
D, L, H, FFN = cfg["d"], cfg["L"], cfg["H"], cfg["ffn"]
V, SEQ = 32000, 2048
opt = optim.adam(1e-4)


def sds(shape, dtype, mesh, spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def abstract_params(model, mesh, ids_shape, spec_fn):
    \"\"\"eval_shape the init (no host buffers) and attach NamedShardings
    chosen by spec_fn(path-matched rules).\"\"\"
    var_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                             jax.ShapeDtypeStruct(ids_shape, jnp.int32))
    params = var_sds["params"]
    specs = spec_fn(params)
    return jax.tree_util.tree_map(
        lambda s, sp: sds(s.shape, s.dtype, mesh, sp), params, specs)


if cfg["mode"] == "tp":
    tp = cfg["tp"]
    dp = n // tp
    B = cfg["bs_group"] * dp
    mesh = pt.make_mesh({"data": dp, "model": tp}, devices=devices)
    model = TransformerLM(vocab=V, dim=D, num_layers=L, num_heads=H,
                          ffn_hidden=FFN, max_len=SEQ)
    rules = parallel.megatron_sp_rules()
    p_sds = abstract_params(model, mesh, (B, SEQ), rules)
    inp_sds = sds((B, SEQ), jnp.int32, mesh, P("data", None))
    tgt_sds = sds((B, SEQ), jnp.int32, mesh, P("data", None))
    if cfg["sp"]:
        # EXPLICIT Megatron tp + sequence-parallel residuals: shard_map
        # with hand-written all_gather / psum_scatter pairs and the CE
        # loss computed inside (parallel/megatron.py) — the pjit
        # partitioner does not produce this lowering (it keeps
        # all-reduces, or splits the residual reshard into all-reduce +
        # all-gather, measured WORSE)
        lm_loss = parallel.make_megatron_sp_lm_apply(
            model, mesh, with_loss=True, comm_dtype=jnp.bfloat16)

        def ce_of(p, inp, tgt):
            return lm_loss({"params": p}, inp, tgt)
    else:
        def ce_of(p, inp, tgt):
            logits = model.apply({"params": p}, inp)
            return jnp.mean(costs.softmax_cross_entropy(
                logits.reshape(-1, V), tgt.reshape(-1)))

    def step(p, inp, tgt):
        def loss_fn(p):
            return ce_of(p, inp, tgt)
        loss, g = jax.value_and_grad(loss_fn)(p)
        # fresh opt state inside the step: zeros-init adds no collectives
        # and the abstract lowering then needs no opt-state shardings
        upd, _ = opt.update(g, opt.init(p), p, jnp.zeros((), jnp.int32))
        return loss, apply_updates(p, upd)

    lowered = jax.jit(step).lower(p_sds, inp_sds, tgt_sds)

else:                                  # mode == "pp": dp x GPipe blocks
    from paddle_tpu.parallel import make_pipeline_loss
    S = cfg["pp"]
    M = cfg["microbatches"]
    dp = n // S
    mbg = cfg["mb_rows_group"] * dp     # global rows per microbatch
    mesh = pt.make_mesh({"data": dp, "pipe": S}, devices=devices)
    model = TransformerLM(vocab=V, dim=D, num_layers=L, num_heads=H,
                          ffn_hidden=FFN, max_len=SEQ)
    assert len(model.blocks) == S
    block0 = model.blocks[0]
    var_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                             jax.ShapeDtypeStruct((2, SEQ), jnp.int32))
    root_name = next(iter(var_sds["params"]))
    root = var_sds["params"][root_name]
    # [S, ...]-stacked block params sharded over pipe (keyed by block0 --
    # Module.apply scoping, the shape make_pipeline_lm_apply's
    # stack_blocks produces); embeddings/head/ln_f replicated, their
    # grads psum over the mesh like any replicated param
    blocks = [root["block%%d" %% i] for i in range(S)]
    stacked_sds = {"block0": jax.tree_util.tree_map(
        lambda *ls: sds((S,) + ls[0].shape, ls[0].dtype, mesh,
                        P(*(("pipe",) + (None,) * ls[0].ndim))), *blocks)}
    emb_sds = jax.tree_util.tree_map(
        lambda s: sds(s.shape, s.dtype, mesh, P()),
        {k: v for k, v in root.items() if not k.startswith("block")})

    def stage_fn(p_stage, act):
        out, _aux = block0.apply({"params": p_stage}, act)
        return out

    def _ln(x, p, eps=1e-6):
        x32 = x.astype(jnp.float32)
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + eps)
        return (y * p["scale"] + p["bias"]).astype(x.dtype)

    def final_fn(fp, outbuf, tgt):
        # per-microbatch CE scan keeps the [mb, T, V] logits transient
        emb_w = fp["emb"]["w"]

        def mb_ce(carry, zt):
            zz, tt = zt
            lg = (_ln(zz, fp["ln_f"]) @ emb_w.T).astype(jnp.float32)
            lse = jax.nn.logsumexp(lg, axis=-1)
            picked = jnp.take_along_axis(lg, tt[..., None],
                                         axis=-1)[..., 0]
            return carry + jnp.sum(lse - picked), None

        # derive the carry from a device-varying value (shard_map
        # varying-axes rule — same trick as pipeline_apply's buffers)
        carry0 = (outbuf.ravel()[0] * 0.0).astype(jnp.float32)
        tot, _ = jax.lax.scan(mb_ce, carry0, (outbuf, tgt))
        return tot

    pipe_loss = make_pipeline_loss(
        mesh, stage_fn, final_fn, pipe_axis="pipe",
        x_spec=P(None, "data", None, None),
        extra_specs=(P(None, "data", None),), reduce_axes=("data",),
        comm_dtype=jnp.bfloat16)

    ids_sds = sds((M, mbg, SEQ), jnp.int32, mesh, P(None, "data", None))
    tgt_sds = sds((M, mbg, SEQ), jnp.int32, mesh, P(None, "data", None))

    def train(stacked, emb_p, ids, tgt):
        def loss_of(stacked, emb_p):
            vars_embed = {"params": {root_name: dict(emb_p)}}
            # embed the 3-D [M, mbg, T] ids DIRECTLY (Embedding takes any
            # int shape; positions passed explicitly so the pos table
            # broadcasts over [M, mbg]) — reshaping [M, mbg(sharded), T]
            # to [M*mbg, T] merges a replicated dim into the dp-sharded
            # one and makes XLA all-gather the whole stack (33 GB/step at
            # n=256, measured)
            h = model.apply(vars_embed, ids,
                            positions=jnp.arange(SEQ)[None, None],
                            method="embed")
            # same emb leaf feeds embed (here) and the head (final_fn):
            # autodiff sums the tied-weight contributions
            return pipe_loss(stacked, emb_p, h, tgt) / (M * mbg * SEQ)
        loss, (gs, ge) = jax.value_and_grad(loss_of, argnums=(0, 1))(
            stacked, emb_p)
        u1, _ = opt.update(gs, opt.init(gs), gs, jnp.zeros((), jnp.int32))
        u2, _ = opt.update(ge, opt.init(ge), ge, jnp.zeros((), jnp.int32))
        return loss, apply_updates(stacked, u1), apply_updates(emb_p, u2)

    lowered = jax.jit(train).lower(stacked_sds, emb_sds, ids_sds, tgt_sds)

import re as _re
pre = lowered.as_text()
# bf16 collective detection in the pre-optimization StableHLO. all_gather
# and collective_permute print on one (long) line with the type at the
# end — match within the line (replica_groups literals grow with the mesh
# and overran a bounded window). reduce_scatter carries a multi-line
# reduction region, so take a wide DOTALL window to its type; our
# programs use a uniform comm dtype, so over-matching is not a concern.
pre_counts = {
    "bf16_all_gather": len(_re.findall(
        r"all_gather.*?bf16", pre)),           # '.' stops at the newline
    "bf16_reduce_scatter": len(_re.findall(
        r"reduce_scatter.{0,100000}?bf16", pre, _re.S)),
    "bf16_collective_permute": len(_re.findall(
        r"collective_permute.*?bf16", pre)),
}
print("=====PREOPT=====")
print(json.dumps(pre_counts))
print("=====HLO=====")
print(lowered.compile().as_text())
"""


def _collect_hlo(n_devices: int, workload: str):
    """Compile the sharded step on a forced n-device CPU platform in a
    subprocess. Returns ``(pre_counts, hlo_text)``: the pre-optimization
    bf16-collective counts (for the comm-compression correction) and the
    optimized post-SPMD HLO."""
    cfg = WORKLOADS[workload]
    if cfg["mode"] == "resnet":
        code = _RESNET_CODE % {"n": n_devices}
    else:
        code = _TRANSFORMER_CODE % {"n": n_devices, "cfg": json.dumps(cfg)}
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=3000)
    if res.returncode != 0:
        raise RuntimeError(f"HLO collection failed (n={n_devices}, "
                           f"{workload}): {res.stderr[-2000:]}")
    pre_counts = {}
    body = res.stdout
    if "=====PREOPT=====" in body:
        pre, body = body.split("=====PREOPT=====", 1)[1].split(
            "=====HLO=====", 1)
        pre_counts = json.loads(pre.strip().splitlines()[0])
    else:
        body = body.split("=====HLO=====", 1)[1]
    return pre_counts, body


def _row(cfg, n, wire, colls=None, extrapolated_from=None,
         grad_wire=None):
    bw = ICI_BYTES_PER_S if n <= ICI_POD_LIMIT else DCN_BYTES_PER_S
    t_comm_ms = wire / bw * 1e3
    t_comp = cfg["t_comp_ms"]
    ovh = cfg.get("overhead_factor", 0.0)
    t_step = t_comp * (1.0 + ovh)
    row = {
        "n_devices": n,
        "wire_bytes_per_device": round(wire),
        "link": "ICI" if n <= ICI_POD_LIMIT else "DCN",
        "t_comp_ms": t_comp,
        "t_comm_ms": round(t_comm_ms, 3),
        "efficiency_no_overlap": round(t_comp / (t_step + t_comm_ms), 4),
        "efficiency_full_overlap": round(
            t_comp / max(t_step, t_comm_ms), 4),
    }
    if grad_wire is not None:
        # middle column: only the GRAD all-reduce overlaps with backward
        # compute (the universally-implemented bucketed grad-sync overlap
        # — XLA's async collective scheduling does this automatically);
        # activation syncs stay on the critical path. Grad sync that
        # exceeds the step can't fully hide — charge the excess.
        t_act = (wire - grad_wire) / bw * 1e3
        t_grad = grad_wire / bw * 1e3
        hidden_excess = max(0.0, t_grad - t_step)
        row["efficiency_grad_overlap"] = round(
            t_comp / (t_step + t_act + hidden_excess), 4)
        row["grad_sync_hides_under_compute"] = bool(t_grad <= t_step)
    if ovh:
        row["compute_overhead_factor"] = round(ovh, 4)
    if colls is not None:
        row["collectives"] = colls
    if extrapolated_from is not None:
        row["extrapolated_from_n"] = extrapolated_from
        row["note"] = ("UPPER BOUND on wire bytes (ring factor taken to "
                       "its g->inf limit: 2B per all-reduce, B otherwise) "
                       "from the largest compiled mesh — fixed-size "
                       "replica groups (e.g. tp) keep constant per-device "
                       "wire, growing groups approach the bound; the XLA "
                       "compile at this mesh size exceeded the harness "
                       "budget. Efficiency is therefore a LOWER bound.")
    return row


def _wire_upper_bound(colls):
    """g->inf limit of the ring factors: 2B for all-reduce, B otherwise.
    >= the true wire at ANY group layout, so efficiencies computed from it
    are lower bounds."""
    total = 0.0
    for kind, e in colls.items():
        total += (2.0 if kind == "all-reduce" else 1.0) * e["buffer_bytes"]
    return total


def project(workload: str, counts=(8, 64, 256)):
    cfg = WORKLOADS[workload]
    rows = []
    last_colls = None
    for n in counts:
        try:
            pre_counts, hlo = _collect_hlo(n, workload)
        except (RuntimeError, subprocess.TimeoutExpired):
            if last_colls is None:
                raise
            colls, nn = last_colls
            rows.append(_row(cfg, n, _wire_upper_bound(colls),
                             extrapolated_from=nn))
            continue
        colls = parse_collectives(hlo, n)
        for kind, pre_key in (
                ("all-gather", "bf16_all_gather"),
                ("reduce-scatter", "bf16_reduce_scatter"),
                ("collective-permute", "bf16_collective_permute")):
            # bf16 comm compression: the jax-level program casts these
            # collectives' operands to bf16 (verified in the
            # pre-optimization StableHLO), but the CPU backend's float
            # normalization upcasts bf16 collectives to f32 in the
            # compiled HLO we parse — on TPU they run native bf16, so
            # halve the parsed wire and record the correction
            if kind in colls and pre_counts.get(pre_key, 0) > 0:
                colls[kind]["wire_bytes_per_device"] *= 0.5
                colls[kind]["bf16_comm_corrected"] = True
        if "collective-permute" in colls and cfg["mode"] == "pp":
            # the ppermute ops live inside the M+S-1-tick wavefront loop:
            # the static HLO op executes once per tick (fwd scan) and once
            # per tick in the transposed bwd scan — scale the parsed
            # static bytes by the tick count
            mult = cfg["microbatches"] + cfg["pp"] - 1
            e = colls["collective-permute"]
            e["wire_bytes_per_device"] *= mult
            e["loop_multiplier"] = mult
        wire = sum(e["wire_bytes_per_device"] for e in colls.values())
        last_colls = (colls, n)
        grad_wire = None
        if cfg.get("all_ar_is_grad") and "all-reduce" in colls:
            # in these workloads the activation syncs are AG/RS/ppermute
            # (explicit shard_map collectives); every all-reduce is a
            # grad/loss sync
            grad_wire = colls["all-reduce"]["wire_bytes_per_device"]
        rows.append(_row(cfg, n, wire, colls=colls, grad_wire=grad_wire))
    return {"workload": workload, "note": cfg["note"], "projection": rows}


def measured_transformer_proxy_n8():
    """MEASURED (executed, not just compiled) dp-sharded transformer step
    on the virtual 8-device CPU mesh vs the same step on 1 device — the
    anchor tying the transformer projection to a real sharded execution.
    Virtual devices share host cores, so the efficiency is a pessimistic
    floor; its value is that the collectives RUN and the sharded step's
    numerics/overheads are real."""
    code = """
import time, json
import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
import paddle_tpu as pt
from paddle_tpu import optim
from paddle_tpu.nn import costs
from paddle_tpu.models import TransformerLM
from paddle_tpu.optim.optimizers import apply_updates

n = int(jax.device_count())
V, D, L, H, FFN, SEQ, BPD = 8000, 256, 4, 2, 1024, 512, 2
model = TransformerLM(vocab=V, dim=D, num_layers=L, num_heads=H,
                      ffn_hidden=FFN, max_len=SEQ)
rng = np.random.RandomState(0)
B = BPD * n
ids = jnp.asarray(rng.randint(0, V, (B, SEQ + 1)), jnp.int32)
mesh = pt.make_mesh({"data": n}, devices=jax.devices()[:n])
inp = jax.device_put(ids[:, :-1], NamedSharding(mesh, P("data", None)))
tgt = jax.device_put(ids[:, 1:], NamedSharding(mesh, P("data", None)))
params = model.init(jax.random.PRNGKey(0), ids[:2, :-1])["params"]
opt = optim.adam(1e-4)
ostate = opt.init(params)

@jax.jit
def step(p, o, inp, tgt):
    def loss_fn(p):
        logits = model.apply({"params": p}, inp)
        return jnp.mean(costs.softmax_cross_entropy(
            logits.reshape(-1, V), tgt.reshape(-1)))
    l, g = jax.value_and_grad(loss_fn)(p)
    u, o2 = opt.update(g, o, p, jnp.zeros((), jnp.int32))
    return apply_updates(p, u), o2, l

params, ostate, l = step(params, ostate, inp, tgt)   # compile+warm
float(l)
iters = 6
t0 = time.perf_counter()
for _ in range(iters):
    params, ostate, l = step(params, ostate, inp, tgt)
float(l)
dt = (time.perf_counter() - t0) / iters
print(json.dumps({"n": n, "ms_per_step": round(dt * 1e3, 1),
                  "tokens_per_s": round(B * SEQ / dt)}))
"""
    out = {}
    for n in (1, 8):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append(f"--xla_force_host_platform_device_count={n}")
        env["XLA_FLAGS"] = " ".join(flags)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        t0 = time.time()
        res = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                             env=env, capture_output=True, text=True,
                             timeout=1800)
        if res.returncode != 0:
            return {"error": res.stderr[-1000:]}
        out[n] = json.loads(res.stdout.strip().splitlines()[-1])
        out[n]["wall_s"] = round(time.time() - t0, 1)
    # per-token throughput ratio: 8-dev tokens/s vs 8x the 1-dev rate
    eff = out[8]["tokens_per_s"] / (8 * out[1]["tokens_per_s"])
    return {
        "model": "TransformerLM d256 L4 seq512, dp=8, bs2/device",
        "n1": out[1], "n8": out[8],
        "efficiency_vs_linear": round(eff, 3),
        "environment": "virtual-cpu-mesh (devices share host cores: "
                       "pessimistic floor; validates the sharded step "
                       "EXECUTES, complements the analytic ICI projection)",
    }


def _dp_only_rows(model_key, t_comp_ms, counts=(8, 64, 256),
                  feasible=True, feasibility_note=""):
    """Analytic dp-only baseline: wire = f32 grad all-reduce only
    (2*P*4*(n-1)/n per device). Same arithmetic the resnet50_dp HLO parse
    is cross-checked against, so no per-model compile is needed.
    ``feasible=False`` keeps the row for context but excludes it from the
    recommendation (e.g. the model + optimizer states + training
    activations exceed single-chip HBM at the comparison batch)."""
    P_count = PARAM_COUNTS[model_key]
    rows = []
    for n in counts:
        wire = 2.0 * P_count * 4 * (n - 1) / n
        rows.append(_row({"t_comp_ms": t_comp_ms}, n, wire,
                         grad_wire=wire))
    note = (f"pure data parallel {model_key} (analytic grad "
            "all-reduce bytes; method cross-checked against the "
            "parsed resnet50_dp HLO)")
    if feasibility_note:
        note += ". " + feasibility_note
    return {"workload": f"{model_key}_dp_only_analytic",
            "feasible": feasible,
            "note": note,
            "projection": rows}


def _recommend(workloads_out):
    """Best config per (model, n) — dp-only baselines included, so tp/pp
    must actually beat dp to be named. Ranked by efficiency_grad_overlap
    (grad syncs hidden under backward — the standard and XLA-automatic
    overlap) with efficiency_no_overlap reported alongside as the
    pessimistic floor."""
    by_model = {
        "transformer_d512": ["transformer_dp_tp", "transformer_dp_tp_sp",
                             "d512_dp_only_analytic"],
        "transformer_d1024": ["transformer_d1024_dp_pp",
                              "d1024_dp_only_analytic"],
        "transformer_d2048": ["transformer_d2048_dp_tp_sp",
                              "d2048_dp_only_analytic"],
    }
    rec = {}
    table = {w["workload"]: w for w in workloads_out}
    for model, names in by_model.items():
        rec[model] = {}
        for n in (8, 64, 256):
            best = None
            for name in names:
                if name not in table:
                    continue
                if not table[name].get("feasible", True):
                    continue
                for row in table[name]["projection"]:
                    if row["n_devices"] == n:
                        eff = row.get("efficiency_grad_overlap",
                                      row["efficiency_no_overlap"])
                        cand = (eff, name, row["efficiency_no_overlap"])
                        if best is None or cand > best:
                            best = cand
            if best:
                rec[model][str(n)] = {
                    "config": best[1],
                    "efficiency_grad_overlap": best[0],
                    "efficiency_no_overlap": best[2]}
    return rec


def main(counts=(8, 64, 256)):
    _fill_t_comp()
    out = {
        "metric": "scaling_efficiency_projection",
        "method": (
            "per-step collective wire bytes parsed from the post-SPMD "
            "optimized HLO of the real sharded train step, compiled on a "
            "forced n-device virtual CPU platform (transformers lower "
            "abstractly — ShapeDtypeStruct args with NamedShardings); "
            "ring-algorithm wire factors; public v5e ICI bandwidth; "
            "measured single-chip step time as t_comp; GPipe bubble "
            "charged as compute overhead; in-loop ppermutes scaled by the "
            "tick count. Numeric correctness of the same collectives is "
            "pinned by __graft_entry__ dryrun (steps 2/4/7) + the "
            "megatron/pipeline-loss oracle tests + the measured proxy "
            "below."),
        "constants": {
            "ici_bytes_per_s_per_chip_oneway": ICI_BYTES_PER_S,
            "dcn_bytes_per_s_per_chip": DCN_BYTES_PER_S,
            "ici_pod_limit_chips": ICI_POD_LIMIT,
            "source": "public TPU v5e spec (1600 Gbit/s ICI per chip)",
        },
        "measured_single_chip_ms": {k: v for k, v in MEASURED_MS.items()},
        "workloads": [],
        "reference_anchor": "3.85x at 4 GPUs, reference benchmark/README.md",
    }
    for w in WORKLOADS:
        out["workloads"].append(project(w, counts=counts))
    out["workloads"].append(
        _dp_only_rows("d512", MEASURED_MS["d512_bs8"], counts))
    out["workloads"].append(
        _dp_only_rows("d1024", MEASURED_MS["d1024_bs16"], counts))
    out["workloads"].append(_dp_only_rows(
        "d2048", 2 * MEASURED_MS["d2048_bs4"], counts,
        feasible=False,
        feasibility_note=(
            "INFEASIBLE at the comparison batch: the bs8 full training "
            "step (params + adam states + activations) OOMs the 16 GB "
            "chip — measured, experiments/profile_transformer.py "
            "PROF_DIM=2048 PROF_BS=8; bs4 runs AT the memory cliff with "
            "no headroom for longer sequences. Kept for wire context; "
            "excluded from the recommendation — d2048-class training "
            "needs the model sharded (tp+sp)")))

    out["recommended"] = _recommend(out["workloads"])

    # cross-check 1: n=8 resnet all-reduce buffer bytes ~= f32 grad size
    rn = out["workloads"][0]["projection"][0]
    ar = rn["collectives"].get("all-reduce", {"buffer_bytes": 0})
    expect = 25.6e6 * 4            # ~25.6M params, f32 grads
    ratio = ar["buffer_bytes"] / expect
    out["cross_check"] = {
        "resnet50_allreduce_buffer_bytes": ar["buffer_bytes"],
        "expected_f32_grad_bytes": expect,
        "ratio": round(ratio, 3),
        "pass": bool(0.8 < ratio < 1.3),
    }
    # cross-check 2: measured virtual-mesh transformer execution at n=8
    out["measured_proxy_transformer_n8"] = measured_transformer_proxy_n8()
    return out


if __name__ == "__main__":
    quick = "--quick" in sys.argv
    result = main(counts=(8,) if quick else (8, 64, 256))
    path = os.path.join(REPO, "SCALING_r05.json")
    # keep the honest virtual-mesh proxy alongside the projection
    prev = os.path.join(REPO, "SCALING_r03.json")
    if os.path.exists(prev):
        with open(prev) as f:
            result["virtual_mesh_proxy_r03"] = json.load(f)
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"metric": result["metric"],
                      "cross_check_pass": result["cross_check"]["pass"],
                      "recommended": result.get("recommended"),
                      "written": path}))
