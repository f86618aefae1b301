"""The behaviour drills: four scripts, each run in a process of its own.

``python tests/drills.py <serving|faults|fleet|spawn>`` runs one drill on
the CPU and prints its verdict as ONE JSON line (the last line of its
output): ``ok`` for the whole, and one block a leg, each with an ``ok``
of its own. ``tests/test_drills.py`` runs each drill once a module, on
two simulated CPU devices, and has one case a leg, so that a red leg
names itself. A drill checks behaviour and counts; nothing here reports
a speed (that is ``benchmarks/run.py``'s, on the chip).

A drill is a process of its own because it needs one: the serving
drill's tensor-parallel leg wants exactly two devices, and the fleet and
spawn drills start replica processes, which a parent that holds an
accelerator must not do.
"""

import json
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp

# a script's path starts at its own directory: add the checkout's root
sys.path.insert(1, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_tpu.obs import xla_cache                       # noqa: E402
from hlo_counts import compiled_all_reduces                # noqa: E402


# ---------------------------------------------------------------------------
# serving gate child (ISSUE 9): continuous batching + paged KV on CPU
# ---------------------------------------------------------------------------

def run_serving_child():
    """The serving runtime's drill: 8 ragged requests through a
    4-slot engine (``paddle_tpu.serve``), once under continuous batching
    and once under the gang-static baseline. Asserts: every request
    completes; ZERO retraces after warmup (one compiled program per
    entry point across all admission/eviction churn); one per-request
    telemetry record each with the TTFT/TPOT SLO fields; continuous
    batching serves the ragged lengths in fewer ticks than static. Then
    six legs, one a serving option. Prints the verdict as one JSON
    line."""
    from paddle_tpu.models import TransformerLM
    from paddle_tpu.obs import InMemorySink, Telemetry
    from paddle_tpu.serve import ContinuousBatchingScheduler, DecodeEngine

    V, W = 64, 32
    model = TransformerLM(vocab=V, dim=32, num_layers=2, num_heads=4,
                          ffn_hidden=64, max_len=W)
    vs = model.init(jax.random.PRNGKey(0), jnp.zeros((1, W), jnp.int32))
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(0, V, rng.randint(2, 8)))
               for _ in range(8)]
    # stragglers dominate their gang: exactly the raggedness
    # iteration-level scheduling exists to absorb
    maxnew = [2, 16, 2, 16, 2, 16, 2, 2]

    def run_policy(policy):
        mem = InMemorySink()
        eng = DecodeEngine(model, vs, max_slots=4, block_size=4,
                           telemetry=Telemetry(sinks=[mem]))

        def one_run():
            sched = ContinuousBatchingScheduler(eng, policy=policy)
            for p, m in zip(prompts, maxnew):
                sched.submit(p, m)
            return sched.run()

        one_run()                          # warmup: compiles + first churn
        warm_ticks = eng.ticks
        done = one_run()                   # counted, fully warm
        return {
            "completed": len(done),
            "tokens": sum(len(r.tokens) for r in done),
            "ticks": eng.ticks - warm_ticks,
            "compile_counts": eng.compile_counts(),
            "request_records": len(mem.by_kind("request")),
            "tick_records": len(mem.by_kind("decode_tick")),
            "sample_request": next(
                (r for r in mem.by_kind("request")
                 if r.get("tpot_ms") is not None), None),
        }

    cont = run_policy("continuous")
    stat = run_policy("static")

    no_retrace = (cont["compile_counts"] == {"prefill": 1, "tick": 1}
                  and stat["compile_counts"] == {"prefill": 1, "tick": 1})
    records_ok = (cont["request_records"] == 16     # warmup + timed runs
                  and cont["sample_request"] is not None
                  and cont["sample_request"].get("ttft_ms") is not None)

    # --- ISSUE 12 leg (a): copy-on-write prefix sharing — a shared-
    # prefix workload admits with FEWER fresh block allocations than
    # sharing-off, produces bit-identical tokens, and leaks nothing
    pre = list(rng.randint(0, V, 9))
    shared_prompts = [pre + list(rng.randint(0, V, 3)) for _ in range(6)]

    def run_shared(share):
        eng = DecodeEngine(model, vs, max_slots=4, block_size=4,
                           share_prefix=share)
        sched = ContinuousBatchingScheduler(eng)
        reqs = [sched.submit(p, 4) for p in shared_prompts]
        sched.run()
        return eng, [r.tokens for r in reqs]

    eng_on, toks_on = run_shared(True)
    eng_off, toks_off = run_shared(False)
    share_leg = {
        "tokens_identical": toks_on == toks_off,
        "fresh_allocs_shared": eng_on.cache.allocator.total_allocs,
        "fresh_allocs_unshared": eng_off.cache.allocator.total_allocs,
        "prefix_hit_blocks": eng_on.cache.prefix_hit_blocks,
        "leak_free": eng_on.cache.free_blocks
        == eng_on.cache.num_blocks - 1,
        "compile_counts": eng_on.compile_counts(),
    }
    share_ok = (share_leg["tokens_identical"] and share_leg["leak_free"]
                and share_leg["fresh_allocs_shared"]
                < share_leg["fresh_allocs_unshared"]
                and share_leg["compile_counts"]
                == {"prefill": 1, "tick": 1})

    # --- ISSUE 12 leg (b): lossless speculative decoding — token-
    # identical to the plain greedy engine with STRICTLY fewer ticks
    def run_spec(k):
        eng = DecodeEngine(model, vs, max_slots=4, block_size=4,
                           speculative=k)
        sched = ContinuousBatchingScheduler(eng)
        reqs = [sched.submit(p, m) for p, m in zip(prompts, maxnew)]
        sched.run()
        return eng, [r.tokens for r in reqs]

    eng_b, toks_b = run_spec(0)
    eng_s, toks_s = run_spec(3)
    spec_leg = {
        "tokens_identical": toks_s == toks_b,
        "ticks_baseline": eng_b.ticks,
        "ticks_speculative": eng_s.ticks,
        "draft_accept_rate": round(
            eng_s.draft_accepted / eng_s.draft_proposed, 4)
        if eng_s.draft_proposed else None,
        "compile_counts": eng_s.compile_counts(),
    }
    spec_ok = (spec_leg["tokens_identical"]
               and spec_leg["ticks_speculative"]
               < spec_leg["ticks_baseline"]
               and spec_leg["compile_counts"]
               == {"prefill": 1, "tick": 1})

    # --- ISSUE 12 leg (c): chunked prefill — a long admission
    # interleaves with running slots' decode ticks (TPOT keeps flowing)
    # instead of stalling them behind one monolithic prefill
    long_prompt = list(rng.randint(0, V, 24))
    short_prompt = list(rng.randint(0, V, 4))

    def run_chunk(chunk):
        eng = DecodeEngine(model, vs, max_slots=2, block_size=4,
                           prefill_chunk=chunk)
        sched = ContinuousBatchingScheduler(eng)
        short = sched.submit(list(short_prompt), 24)
        for _ in range(3):
            sched.step()
        before = len(short.tokens)
        long_req = sched.submit(long_prompt, 2)
        while long_req.first_token_ts is None and sched.step():
            pass
        interleaved = len(short.tokens) - before
        sched.run()
        return interleaved, short.tokens, long_req.tokens, eng

    il_chunk, short_c, long_c, eng_ck = run_chunk(6)
    il_full, short_f, long_f, _ = run_chunk(None)
    chunk_leg = {
        "interleaved_tokens_chunked": il_chunk,
        "interleaved_tokens_monolithic": il_full,
        "tokens_identical": short_c == short_f and long_c == long_f,
        "prefill_chunks": eng_ck.prefill_chunks,
        "compile_counts": eng_ck.compile_counts(),
    }
    chunk_ok = (chunk_leg["tokens_identical"]
                and chunk_leg["interleaved_tokens_chunked"]
                > chunk_leg["interleaved_tokens_monolithic"]
                and chunk_leg["compile_counts"]
                == {"prefill": 1, "tick": 1})

    # --- ISSUE 14 leg (d): int8 KV quantization — at EQUAL pool bytes
    # the int8 pool serves >= 1.8x the resident sequences, a saturated
    # workload still completes every request, and greedy tokens agree
    # >= 99% with the f32 pool on the gate set (bounded drift)
    res_len, res_reserve = 5, 12            # 3 blocks per sequence
    from paddle_tpu.serve import PagedKVCache

    def pool_blocks(kv_dtype, budget_bytes):
        probe = PagedKVCache(num_layers=2, num_heads=4, head_dim=8,
                             num_blocks=2, block_size=4, max_slots=1,
                             max_blocks_per_seq=8, kv_dtype=kv_dtype)
        return budget_bytes // probe.bytes_per_block, \
            probe.kv_bytes_per_token

    budget = pool_blocks(None, 0)[1] * 4 * (6 * 3)   # 6 f32 sequences

    def count_resident(kv_dtype):
        nb, bpt = pool_blocks(kv_dtype, budget)
        eng = DecodeEngine(model, vs, max_slots=16, block_size=4,
                           num_blocks=nb + 1, kv_dtype=kv_dtype)
        resident = 0
        while (eng.free_slots()
               and eng.can_admit(res_reserve)):
            slot = eng.free_slots()[0]
            eng.admit(slot, list(rng.randint(0, V, res_len)),
                      reserve_len=res_reserve)
            resident += 1
        return resident, nb, bpt

    res_f32, nb_f32, bpt_f32 = count_resident(None)
    res_i8, nb_i8, bpt_i8 = count_resident("int8")

    def run_quant(kv_dtype):
        eng = DecodeEngine(model, vs, max_slots=4, block_size=4,
                           kv_dtype=kv_dtype)
        sched = ContinuousBatchingScheduler(eng)
        reqs = [sched.submit(p, m) for p, m in zip(prompts, maxnew)]
        sched.run()
        return [r.tokens for r in reqs], eng

    toks_f32, _ = run_quant(None)
    toks_i8, eng_i8 = run_quant("int8")
    agree = sum(a == b for x, y in zip(toks_f32, toks_i8)
                for a, b in zip(x, y))
    total = sum(len(x) for x in toks_f32)
    quant_leg = {
        "pool_budget_bytes": int(budget),
        "resident_f32": res_f32, "resident_int8": res_i8,
        "capacity_ratio": round(res_i8 / res_f32, 3) if res_f32 else None,
        "kv_bytes_per_token_f32": int(bpt_f32),
        "kv_bytes_per_token_int8": int(bpt_i8),
        "completed": sum(1 for t in toks_i8 if t),
        "token_agreement": round(agree / total, 4) if total else None,
        "compile_counts": eng_i8.compile_counts(),
    }
    quant_ok = (quant_leg["capacity_ratio"] is not None
                and quant_leg["capacity_ratio"] >= 1.8
                and quant_leg["completed"] == 8
                and quant_leg["token_agreement"] >= 0.99
                and quant_leg["compile_counts"]
                == {"prefill": 1, "tick": 1})

    # --- ISSUE 14 leg (e): radix retention — a SECOND wave of
    # same-prefix sessions (no live sharer) hits retained blocks and
    # allocates fewer fresh blocks than a retention-off engine; the
    # pool stays leak-free with retained counted reclaimable
    ret_pre = list(rng.randint(0, V, 8))
    ret_tails = [list(rng.randint(0, V, 3)) for _ in range(4)]

    def run_retention(retain):
        eng = DecodeEngine(model, vs, max_slots=2, block_size=4,
                           retain_prefix=retain)
        allocs = []
        for i in range(2):               # two sequential waves
            sched = ContinuousBatchingScheduler(eng)
            for t in ret_tails[2 * i:2 * i + 2]:
                sched.submit(ret_pre + list(t), 4)
            sched.run()
            allocs.append(eng.cache.allocator.total_allocs)
        return eng, allocs[1] - allocs[0]      # wave-2 fresh allocs

    eng_ret, wave2_on = run_retention(True)
    eng_off2, wave2_off = run_retention(False)
    ret_leg = {
        "retained_hits": eng_ret.cache.retained_hits,
        "wave2_fresh_allocs_retained": wave2_on,
        "wave2_fresh_allocs_unretained": wave2_off,
        "retained_blocks_now": eng_ret.cache.retained_blocks,
        "leak_free": eng_ret.cache.free_blocks
        == eng_ret.cache.num_blocks - 1,
        "compile_counts": eng_ret.compile_counts(),
    }
    ret_ok = (ret_leg["retained_hits"] >= 1
              and ret_leg["wave2_fresh_allocs_retained"]
              < ret_leg["wave2_fresh_allocs_unretained"]
              and ret_leg["leak_free"]
              and ret_leg["compile_counts"] == {"prefill": 1, "tick": 1})

    # --- ISSUE 15 leg (f): tensor-parallel sharded tick — the tp=2
    # engine (2 forced host devices) is token-identical to the
    # single-device engine on the ragged churn workload across TWO
    # waves on one engine (wave 2 pins zero retraces), per-shard KV
    # bytes halve (capacity at equal per-device pool bytes doubles),
    # and the compiled tp tick holds the partitioner's all-reduces.
    from jax.sharding import Mesh
    tp_mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))

    def run_tp(mesh):
        eng = DecodeEngine(model, vs, max_slots=4, block_size=4,
                           mesh=mesh)
        toks = []
        for _ in range(2):
            sched = ContinuousBatchingScheduler(eng)
            reqs = [sched.submit(p, m) for p, m in zip(prompts, maxnew)]
            sched.run()
            toks.append([r.tokens for r in reqs])
        return toks, eng

    toks_tp, eng_tp = run_tp(tp_mesh)
    toks_1d, eng_1d = run_tp(None)
    tp_all_reduces = compiled_all_reduces(
        eng_tp.lower_tick().compile().as_text())
    tp_leg = {
        "tokens_identical": toks_tp == toks_1d,
        "tp_degree": eng_tp.tp_degree,
        "compile_counts": eng_tp.compile_counts(),
        "kv_bytes_per_token_tp": eng_tp.cache.kv_bytes_per_token,
        "kv_bytes_per_token_1dev": eng_1d.cache.kv_bytes_per_token,
        # per-shard capacity ratio: blocks a device's HBM budget holds
        # under tp vs alone (the head split's whole capacity story)
        "per_shard_capacity_ratio": round(
            eng_1d.cache.kv_bytes_per_token
            / eng_tp.cache.kv_bytes_per_token, 3),
        "tick_all_reduces": tp_all_reduces,
        "leak_free": eng_tp.cache.free_blocks
        == eng_tp.cache.num_blocks - 1,
    }
    tp_ok = (tp_leg["tokens_identical"] and tp_leg["tp_degree"] == 2
             and tp_leg["compile_counts"] == {"prefill": 1, "tick": 1}
             and tp_leg["per_shard_capacity_ratio"] >= 2.0
             and tp_leg["tick_all_reduces"] >= 1
             and tp_leg["leak_free"])

    cont_ok = (cont["completed"] == 8 and stat["completed"] == 8
               and no_retrace and records_ok
               and cont["ticks"] < stat["ticks"])
    ok = (cont_ok and share_ok and spec_ok and chunk_ok and quant_ok
          and ret_ok and tp_ok)
    print(json.dumps({
        "child": "serving", "ok": bool(ok),
        "requests": 8, "max_slots": 4, "block_size": 4,
        "continuous_beats_static": {
            "ok": bool(cont_ok), "continuous": cont, "static": stat,
            "zero_retraces_after_warmup": bool(no_retrace),
            "request_records_ok": bool(records_ok)},
        "prefix_sharing": {**share_leg, "ok": bool(share_ok)},
        "speculative": {**spec_leg, "ok": bool(spec_ok)},
        "chunked_prefill": {**chunk_leg, "ok": bool(chunk_ok)},
        "quantization": {**quant_leg, "ok": bool(quant_ok)},
        "retention": {**ret_leg, "ok": bool(ret_ok)},
        "tp": {**tp_leg, "ok": bool(tp_ok)},
        "device": jax.devices()[0].device_kind,
    }))
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# elastic fault-tolerance gate child (ISSUE 10): supervised crash/corrupt/
# preempt recovery on CPU, bit-equal to the uninterrupted run
# ---------------------------------------------------------------------------

def run_faults_child():
    """The resilience layer's CI gate: a tiny fused transformer training
    run under ``run_resilient`` with a seeded :class:`FaultSchedule`,
    three legs —

    - **crash+resume**: an injected crash mid pass 2; the supervisor
      restarts, ``resume=True`` picks up the newest checkpoint, and the
      final params are BIT-EQUAL (f32) to the uninterrupted 3-pass run.
    - **corrupt latest pass**: pass 1's landed checkpoint gets a byte
      flipped (CRC now stale), then a crash in pass 2; the resume
      quarantines ``pass-00001`` to ``pass-00001.corrupt`` (never
      deletes), falls back to pass 0, replays, and still finishes
      bit-equal.
    - **preempt mid-pass**: an injected preemption quiesces at the next
      group boundary, writes a mid-pass checkpoint, and exits with the
      distinct ``"preempted"`` status; a second supervised run resumes
      from it and finishes bit-equal.

    Prints the verdict as one JSON line."""
    import glob
    import tempfile
    from paddle_tpu import optim
    from paddle_tpu.models import TransformerLM
    from paddle_tpu.nn import costs
    from paddle_tpu.train import FaultSchedule, Trainer, run_resilient

    V, T, bs, n_batches = 64, 16, 8, 8
    rng = np.random.RandomState(0)
    batches = [{"x": rng.randint(0, V, (bs, T)).astype(np.int32),
                "y": rng.randint(0, V, (bs, T)).astype(np.int32)}
               for _ in range(n_batches)]
    reader = lambda: iter(batches)       # noqa: E731 - deterministic replay

    def make_tr(faults=None):
        tr = Trainer(
            model=TransformerLM(vocab=V, dim=32, num_layers=2, num_heads=4,
                                ffn_hidden=64, max_len=T),
            loss_fn=lambda out, b: costs.softmax_cross_entropy(
                out.reshape(-1, V), b["y"].reshape(-1)),
            optimizer=optim.adam(1e-3), steps_per_call=2, faults=faults)
        tr.init(jax.random.PRNGKey(0), batches[0])
        return tr

    def leaves(state):
        return jax.tree_util.tree_leaves(jax.device_get(state.params))

    def equal(a, b):
        return all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(a, b))

    root = tempfile.mkdtemp(prefix="paddle_tpu_faults_")
    passes, steps_per_pass = 3, n_batches          # M=1: one step per batch

    base = make_tr()
    base.train(reader, num_passes=passes,
               checkpoint_dir=os.path.join(root, "base"), log_period=0)
    p0 = leaves(base.train_state)

    # leg A: crash mid pass 2 -> restart -> resume -> bit-equal. ONE
    # schedule instance shared across attempts: the one-shot disarm is
    # what makes the fault transient (a fresh schedule per attempt would
    # model a deterministic bug — give-up-loud territory).
    crash_step = 2 * steps_per_pass + 3
    fs_a = FaultSchedule(crash_at_step=crash_step)
    res_a = run_resilient(
        lambda: make_tr(fs_a), reader,
        checkpoint_dir=os.path.join(root, "crash"), num_passes=passes,
        log_period=0, backoff_s=0.01)
    leg_a = {"status": res_a.status, "restarts": res_a.restarts,
             "params_equal": equal(p0, leaves(res_a.state))}

    # leg B: corrupt pass-1's checkpoint (save idx 1), crash in pass 2 ->
    # quarantine + fall back one pass -> bit-equal
    ck_b = os.path.join(root, "corrupt")
    fs_b = FaultSchedule(corrupt_checkpoint_file=1,
                         crash_at_step=crash_step)
    res_b = run_resilient(
        lambda: make_tr(fs_b), reader,
        checkpoint_dir=ck_b, num_passes=passes, log_period=0,
        backoff_s=0.01)
    leg_b = {"status": res_b.status, "restarts": res_b.restarts,
             "fallbacks": len(res_b.fallbacks),
             "corrupt_dirs": len(glob.glob(os.path.join(ck_b,
                                                        "*.corrupt*"))),
             "params_equal": equal(p0, leaves(res_b.state))}

    # leg C: preempt mid pass 1 (graceful stop at the group boundary,
    # quiesced mid-pass checkpoint) -> distinct status -> resume finishes
    ck_c = os.path.join(root, "preempt")
    fs_c = FaultSchedule(preempt_at_step=steps_per_pass + 3)
    res_c1 = run_resilient(
        lambda: make_tr(fs_c),
        reader, checkpoint_dir=ck_c, num_passes=passes, saving_period=4,
        log_period=0, backoff_s=0.01)
    res_c2 = run_resilient(
        make_tr, reader, checkpoint_dir=ck_c, num_passes=passes,
        saving_period=4, log_period=0, backoff_s=0.01)
    leg_c = {"first_status": res_c1.status,
             "preempt_next_batch": (res_c1.preempted.next_batch
                                    if res_c1.preempted else None),
             "second_status": res_c2.status,
             "params_equal": equal(p0, leaves(res_c2.state))}

    leg_a["ok"] = bool(leg_a["status"] == "completed"
                       and leg_a["restarts"] == 1 and leg_a["params_equal"])
    leg_b["ok"] = bool(leg_b["status"] == "completed"
                       and leg_b["restarts"] == 1
                       and leg_b["fallbacks"] >= 1
                       and leg_b["corrupt_dirs"] >= 1
                       and leg_b["params_equal"])
    leg_c["ok"] = bool(leg_c["first_status"] == "preempted"
                       and leg_c["second_status"] == "completed"
                       and leg_c["params_equal"])
    ok = leg_a["ok"] and leg_b["ok"] and leg_c["ok"]
    print(json.dumps({
        "child": "faults", "ok": bool(ok),
        "passes": passes, "steps_per_pass": steps_per_pass,
        "crash": leg_a, "corrupt": leg_b, "preempt": leg_c,
        "device": jax.devices()[0].device_kind,
    }))
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# serving-fleet resilience gate child (ISSUE 11): loadgen burst over 3
# in-process replicas with one injected kill + one drain, plus the
# SJF-vs-FCFS goodput differential under a deterministic clock
# ---------------------------------------------------------------------------

def run_fleet_child():
    """The serving fleet's drill, on a SimClock. The first two parts are
    the verdict's ``inprocess`` leg; ``process``, ``tracing``, ``disagg``
    and ``chaos`` follow —

    - **fault drill**: a seeded bursty loadgen trace (sessions with
      shared prefixes, ragged lengths, deadlines) over 3 replicas; a
      FaultSchedule kills replica 0 mid-decode and replica 1 is drained
      mid-traffic. Asserts: every request reaches a terminal
      finish_reason with exactly one terminal record per rid (retried
      lineage for the killed replica's requests), p99 TTFT finite, the
      shed count bounded, zero retraces and zero leaked KV blocks on
      every surviving replica.
    - **SLO policy differential**: the same overload (2 long jobs ahead
      of 4 short deadline-carrying jobs, one engine, fixed 1s ticks)
      under order="fcfs" vs order="sjf" — SJF's goodput-under-deadline
      must beat FCFS's, reported through the new percentile metrics.
    - **process-isolation drill** (ISSUE 13): two replicas as REAL
      child processes behind the submit/complete transport; the
      schedule hangs one transport reply (per-message timeout +
      retransmit recovers the cached reply), garbles another
      (classified corrupt, recovered), then SIGKILLs replica 0
      mid-decode — the router never crashes, death is observed via
      heartbeat staleness, every request stays terminal with one
      terminal record per rid and oracle-identical tokens, the live
      survivors are leak- and retrace-free (evidence from each child's
      own stats probe), and the autoscaler cold-spawns a replacement
      within its restart budget.
    - **observability drill** (ISSUE 17): the SAME process-mode
      SIGKILL-resubmit shape run twice — once fully instrumented
      (tracing + SLO + serving anomaly detection + child telemetry
      JSONL sinks), once with everything off. Asserts the merged fleet
      trace JSON-round-trips with ≥2 replica lanes plus the router
      lane and the killed-and-resubmitted rid renders as ONE connected
      s→t→f flow across processes; the streaming SLO report has finite
      percentiles and a burn rate in ``stats()``; an injected stall
      fires the ``tick_stall`` anomaly and dumps a forensic bundle;
      the killed child's JSONL telemetry survives its SIGKILL; and the
      instrumented run's tokens and finish reasons are IDENTICAL to
      the dark run's — observability changes nothing it observes.
    - **disaggregation drill** (ISSUE 18): 1 prefill + 2 decode
      replicas as SOCKET children on loopback — every request prefills
      on the prefill replica, streams its KV pages over TCP and decodes
      the greedy oracle's exact tokens, with the handoff wire bytes
      matching the analytic blocks x bytes-per-block accounting; then
      in-process role fleets measure the disaggregation CLAIM (decode
      tokens/tick within 25% when heavy prefill-only load is added) and
      the int8 path (identical tokens to colocated int8, ~2.7x fewer
      wire bytes per block than f32).
    - **chaos drill** (ISSUE 20): the disagg socket fleet again, under
      a seeded :class:`NetworkChaos` plane — an asymmetric partition
      cuts the prefill replica's reply direction (false death → fence
      by epoch → disagg degrades to colocated prefill on the decoders)
      and a one-shot link flap fences a decode replica. Asserts every
      request terminal with oracle tokens and a single lineage, zero
      tokens from any fenced epoch, both zombies re-admitted on heal,
      the degradation engaged AND released, survivors leak-free, and
      the chaos fleet's ``stats()`` keyset differing from the chaos-off
      socket fleet's (leg 5a — the dark twin) by exactly ``{"chaos"}``.

    Prints the verdict as one JSON line."""
    import collections
    import tempfile
    from paddle_tpu.models import TransformerLM
    from paddle_tpu.obs import InMemorySink, Telemetry, summarize_requests
    from paddle_tpu.serve import (Autoscaler, ContinuousBatchingScheduler,
                                  DecodeEngine, ServingFleet, SimClock)
    from paddle_tpu.serve.loadgen import make_workload, workload_stats
    from paddle_tpu.train import FaultSchedule

    V, W = 64, 32
    model = TransformerLM(vocab=V, dim=32, num_layers=2, num_heads=4,
                          ffn_hidden=64, max_len=W)
    vs = model.init(jax.random.PRNGKey(0), jnp.zeros((1, W), jnp.int32))

    # -- leg 1: the fleet fault drill
    mem = InMemorySink()
    clock = SimClock()
    faults = FaultSchedule(kill_replica_at_tick=(6, 0))
    fleet = ServingFleet.from_model(
        model, vs, 3, engine_kwargs=dict(max_slots=2, block_size=4),
        telemetry=Telemetry(sinks=[mem]), clock=clock,
        heartbeat_timeout_s=0.25, est_tick_s=0.1, faults=faults,
        root=tempfile.mkdtemp(prefix="paddle_tpu_fleet_gate_"))
    wl = make_workload(14, V, seed=3, rate_rps=30.0, arrival="bursty",
                       prompt_len=(2, 8), max_new=(2, 10), n_sessions=3,
                       session_prefix_len=4, p_session=0.5,
                       deadline_s=(2.0, 6.0), p_deadline=0.5,
                       max_total=W)
    frs = fleet.play(wl, dt_s=0.1, drain_at_tick={10: 1})
    stats = fleet.stats()
    summary = summarize_requests(mem.records)

    all_terminal = all(fr.record is not None for fr in frs)
    terminal_per_rid = collections.Counter(
        r["rid"] for r in mem.by_kind("request")
        if r["finish_reason"] != "retried")
    lineage_ok = (set(terminal_per_rid) == {fr.rid for fr in frs}
                  and all(v == 1 for v in terminal_per_rid.values()))
    survivors = [w for w in fleet.workers if not w.killed
                 and w.state != "dead"]
    no_leak = all(w.engine.cache.free_blocks
                  == w.engine.cache.num_blocks - 1 for w in survivors)
    no_retrace = all(
        w.engine.compile_counts() == {"prefill": 1, "tick": 1}
        for w in survivors if w.engine.ticks > 0)
    p99_finite = (summary["ttft_ms_p99"] is not None
                  and np.isfinite(summary["ttft_ms_p99"]))
    shed_bounded = 0 <= stats["shed"] <= len(frs) // 2

    # -- leg 2: SJF vs FCFS goodput differential (single engine, 1s ticks)
    def run_order(order):
        mem2 = InMemorySink()
        eng = DecodeEngine(model, vs, max_slots=2, block_size=4,
                           telemetry=Telemetry(sinks=[mem2]))
        clk = SimClock()
        sched = ContinuousBatchingScheduler(eng, order=order, clock=clk,
                                            est_tick_s=1.0)
        rng = np.random.RandomState(0)
        for _ in range(2):                         # stragglers first
            sched.submit(list(rng.randint(1, V, 4)), 12)
        for _ in range(4):                         # tight-deadline shorts
            sched.submit(list(rng.randint(1, V, 4)), 2, deadline_s=8.0)
        while sched.step():
            clk.advance(1.0)
        return summarize_requests(mem2.records)

    fcfs = run_order("fcfs")
    sjf = run_order("sjf")
    sjf_wins = (fcfs["goodput_pct"] is not None
                and sjf["goodput_pct"] is not None
                and sjf["goodput_pct"] > fcfs["goodput_pct"])

    # -- leg 3: process-isolated replicas + supervised autoscaler
    # (ISSUE 13). Transport faults first (hang -> timeout+retransmit,
    # corrupt -> classified+retransmit), then SIGKILL replica 0
    # mid-decode; min_replicas=2 makes the autoscaler cold-spawn a
    # replacement child when the death is observed.
    oracle_fwd = jax.jit(lambda v, i: model.apply(v, i))

    def greedy_oracle(prompt, n_new):
        seq, out = list(prompt), []
        for _ in range(n_new):
            pad = np.zeros((1, W), np.int32)
            pad[0, :len(seq)] = seq
            logits = oracle_fwd(vs, jnp.asarray(pad))
            tok = int(np.argmax(np.asarray(logits[0, len(seq) - 1])))
            out.append(tok)
            seq.append(tok)
        return out

    mem3 = InMemorySink()
    clock3 = SimClock()
    faults3 = FaultSchedule(sigkill_replica_at_tick=(6, 0),
                            transport_hang_at=(3, 1),
                            corrupt_reply_at=(4, 1))
    scaler = Autoscaler(min_replicas=2, max_replicas=3, up_delay_s=60.0,
                        idle_grace_ticks=1000, cooldown_ticks=5,
                        max_replacements=1)
    fleet3 = ServingFleet.from_model(
        model, vs, 2, engine_kwargs=dict(max_slots=2, block_size=4),
        replica_mode="process", telemetry=Telemetry(sinks=[mem3]),
        clock=clock3, heartbeat_timeout_s=0.25, est_tick_s=0.1,
        # generous per-message budget: a child's FIRST tick includes
        # its jit compiles, and a slow CI host must not turn that into
        # a false transport_down (only the injected hang pays it)
        faults=faults3, transport_timeout_s=5.0, autoscaler=scaler,
        root=tempfile.mkdtemp(prefix="paddle_tpu_fleet_proc_"))
    wl3 = make_workload(8, V, seed=7, rate_rps=30.0, prompt_len=(2, 6),
                        max_new=(3, 8), max_total=W)
    try:
        frs3 = fleet3.play(wl3, dt_s=0.1)
        stats3 = fleet3.stats()
        term3 = collections.Counter(
            r["rid"] for r in mem3.by_kind("request")
            if r["finish_reason"] != "retried")
        proc_all_terminal = all(fr.record is not None for fr in frs3)
        proc_lineage = (set(term3) == {fr.rid for fr in frs3}
                        and all(v == 1 for v in term3.values()))
        retried3 = [fr for fr in frs3 if fr.retries > 0]
        # re-homed requests regenerate the oracle's exact tokens —
        # process isolation is semantically invisible
        oracle_ok = all(
            fr.tokens == greedy_oracle(fr.prompt, fr.max_new_tokens)
            for fr in (retried3[:2] or frs3[:2]))
        probes = {w.replica_id: w.stats_probe(clock3())
                  for w in fleet3.workers
                  if w.state == "live" and not w.killed}
        proc_no_leak = bool(probes) and all(
            p is not None and p["free_blocks"] == p["num_blocks"] - 1
            for p in probes.values())
        proc_no_retrace = all(
            p["compile_counts"] == {"prefill": 1, "tick": 1}
            for p in probes.values()
            if p is not None and p["ticks"] > 0)
        transports = {w.replica_id: w.transport_stats()
                      for w in fleet3.workers
                      if w.transport_stats() is not None}
        hang_recovered = any(t["timeouts"] >= 1 and t["retransmits"] >= 1
                             for t in transports.values())
        corrupt_classified = any(t["corrupt_replies"] >= 1
                                 for t in transports.values())
        replaced = any(e["action"] == "replace" for e in scaler.events)
        proc = {
            "ok": bool(proc_all_terminal and proc_lineage and oracle_ok
                       and proc_no_leak and proc_no_retrace
                       and hang_recovered and corrupt_classified
                       and replaced
                       and stats3["stale_completions"] == 0
                       and stats3["resubmits"] >= 1
                       and scaler.replacements <= 1),
            "all_terminal": bool(proc_all_terminal),
            "lineage_ok": bool(proc_lineage),
            "oracle_tokens_ok": bool(oracle_ok),
            "no_leak_on_survivors": bool(proc_no_leak),
            "zero_retraces_on_survivors": bool(proc_no_retrace),
            "transport_hang_recovered": bool(hang_recovered),
            "corrupt_reply_classified": bool(corrupt_classified),
            "replacement_spawned": bool(replaced),
            "replacements_within_budget": scaler.replacements,
            "retried_requests": len(retried3),
            "transports": transports,
            "scale_events": [{k: e[k] for k in
                              ("action", "reason", "tick",
                               "replicas_before", "replicas_after")}
                             for e in scaler.events],
            "stats": stats3,
            "faults_fired": [p for p, _ in faults3.fired],
        }
    finally:
        fleet3.shutdown()

    # -- leg 4: fleet observability drill (ISSUE 17) — the same
    # SIGKILL-resubmit shape traced and dark, compared
    from paddle_tpu.obs import ServingAnomalyDetector
    from paddle_tpu.obs.fleet_trace import flow_connected, lane_monotonic

    def run_obs_drill(instrumented):
        mem4 = InMemorySink()
        clock4 = SimClock()
        faults4 = FaultSchedule(sigkill_replica_at_tick=(6, 0),
                                stall_replica_at_tick=(8, 1, 3))
        root4 = tempfile.mkdtemp(prefix="paddle_tpu_fleet_obs_")
        anom = (ServingAnomalyDetector(
                    out_dir=os.path.join(root4, "anomalies"),
                    stall_ticks=2)
                if instrumented else None)
        # heartbeat timeout ABOVE the injected stall (3 ticks = 0.3s
        # plus the wake tick): the stall must fire the tick_stall
        # anomaly, not the death verdict — replica 1 is the sole
        # survivor once replica 0 is SIGKILLed
        f = ServingFleet.from_model(
            model, vs, 2, engine_kwargs=dict(max_slots=2, block_size=4),
            replica_mode="socket", telemetry=Telemetry(sinks=[mem4]),
            clock=clock4, heartbeat_timeout_s=0.55, est_tick_s=0.1,
            faults=faults4, transport_timeout_s=5.0, root=root4,
            trace=instrumented, slo=instrumented, anomaly=anom,
            metrics=instrumented,
            telemetry_dir=(os.path.join(root4, "child_telemetry")
                           if instrumented else None))
        wl4 = make_workload(8, V, seed=7, rate_rps=30.0,
                            prompt_len=(2, 6), max_new=(3, 8),
                            max_total=W)
        scrape = None
        try:
            frs4 = f.play(wl4, dt_s=0.1)
            if instrumented:
                # remote scrape over the live socket: the survivor
                # (replica 0 was SIGKILLed) serves its own registry as
                # text exposition via the `metrics` transport op
                scrape = f.workers[1].scrape_metrics(clock4())
        finally:
            f.shutdown()
        return f, frs4, anom, root4, scrape

    fleet_tr, frs_tr, anom4, root_tr, scrape4 = run_obs_drill(True)
    fleet_dk, frs_dk, _, _, _ = run_obs_drill(False)

    trace4 = fleet_tr.fleet_trace()
    trace4 = json.loads(json.dumps(trace4))      # Chrome-parseable
    lanes = sorted({e.get("pid") for e in trace4["traceEvents"]
                    if e.get("ph") != "M"})
    lanes_ok = 0 in lanes and len([p for p in lanes if p > 0]) >= 2
    retried4 = [fr.rid for fr in frs_tr if fr.retries > 0]
    resub_flow_ok = bool(retried4) and all(
        flow_connected(trace4, r) for r in retried4)
    slo4 = fleet_tr.slo_report()
    stats4 = fleet_tr.stats()
    slo_ok = (slo4["wall_ms_p99"] is not None
              and np.isfinite(slo4["wall_ms_p99"])
              and "burn_rate" in stats4.get("slo", {}))
    stall_fired = any(v.kind == "tick_stall" for v in anom4.verdicts)
    bundle_ok = stall_fired and any(
        "tick_stall" in d for d in (
            os.listdir(os.path.join(root_tr, "anomalies"))
            if os.path.isdir(os.path.join(root_tr, "anomalies"))
            else []))
    # the SIGKILLed child's line-flushed JSONL outlives its process
    killed_jsonl = os.path.join(root_tr, "child_telemetry",
                                "replica_0.jsonl")
    jsonl_ok = (os.path.isfile(killed_jsonl)
                and os.path.getsize(killed_jsonl) > 0)
    # instrumentation must not change the work: identical tokens and
    # finish reasons per rid against the dark run
    tok_tr = {fr.rid: (fr.finish_reason, list(fr.tokens))
              for fr in frs_tr}
    tok_dk = {fr.rid: (fr.finish_reason, list(fr.tokens))
              for fr in frs_dk}
    dark_identical = tok_tr == tok_dk
    # metrics backbone (ISSUE 19): the instrumented socket drill's
    # merged registry must hold per-link RTT histograms with nonzero
    # counts for every link (parent-side wire health), per-replica
    # engine tick histograms absorbed from the children's piggybacked
    # deltas, and a parseable Prometheus exposition; the dark twin must
    # carry no registry and — beyond the slo/anomaly blocks the
    # instrumented run opts into — no new stats keys.
    from paddle_tpu.obs.metrics import parse_exposition
    snapm = fleet_tr.metrics.snapshot()

    def _hist_count(name, lkey, lval):
        return sum(r.get("count") or 0 for r in snapm
                   if r["name"] == name
                   and r["labels"].get(lkey) == lval)

    links_ok = all(_hist_count("transport_rtt_ms", "link", l) > 0
                   for l in ("0", "1"))
    ticks_ok = all(_hist_count("engine_tick_ms", "replica", r) > 0
                   for r in ("0", "1"))
    expo4 = parse_exposition(fleet_tr.metrics.render())
    expo_ok = (len(expo4["samples"]) > 0
               and expo4["types"].get("transport_rtt_ms") == "histogram"
               and expo4["types"].get("fleet_ticks") == "counter")
    scraped = parse_exposition(scrape4 or "")
    scrape_ok = (len(scraped["samples"]) > 0
                 and scraped["types"].get("engine_ticks") == "counter")
    new_keys = set(stats4) - set(fleet_dk.stats())
    keys_ok = (new_keys == {"slo", "anomalies"}
               and fleet_dk.metrics is None)
    metrics4 = {
        "ok": bool(links_ok and ticks_ok and expo_ok and scrape_ok
                   and keys_ok),
        "remote_scrape_samples": len(scraped["samples"]),
        "per_link_rtt_counts": {
            l: _hist_count("transport_rtt_ms", "link", l)
            for l in ("0", "1")},
        "per_replica_tick_counts": {
            r: _hist_count("engine_tick_ms", "replica", r)
            for r in ("0", "1")},
        "exposition_samples": len(expo4["samples"]),
        "new_stats_keys": sorted(new_keys),
        "registry_rows": len(snapm),
    }
    tracing = {
        "ok": bool(lanes_ok and resub_flow_ok and slo_ok and bundle_ok
                   and jsonl_ok and dark_identical and metrics4["ok"]
                   and lane_monotonic(trace4)),
        "metrics": metrics4,
        "lanes": lanes,
        "resubmitted_rids": retried4,
        "resubmit_flow_connected": bool(resub_flow_ok),
        "lane_monotonic": bool(lane_monotonic(trace4)),
        "trace_events": len(trace4["traceEvents"]),
        "slo": {k: slo4[k] for k in
                ("requests", "goodput_pct", "burn_rate", "ttft_ms_p99",
                 "wall_ms_p99")},
        "tick_stall_fired": bool(stall_fired),
        "anomaly_bundle": bool(bundle_ok),
        "killed_child_jsonl_survives": bool(jsonl_ok),
        "identical_to_uninstrumented": bool(dark_identical),
    }

    # -- leg 5: prefill/decode disaggregation (ISSUE 18) — sockets on
    # loopback for the real cross-host shape, in-process fleets for the
    # cheap differential measurements.
    #
    # 5a: 1 prefill + 2 decode replicas as socket children. Every
    # request must prefill on the prefill replica, stream its KV pages
    # over TCP, and decode to the greedy oracle's EXACT tokens; the
    # wire bytes must equal blocks x the analytic per-block size.
    f32_block = 2 * 2 * 4 * 4 * 8 * 4       # 2(kv) L H BS hd f32
    int8_block = 2 * 2 * 4 * 4 * (8 + 4)    # int8 values + f32 scales
    sock_fleet = ServingFleet.from_model(
        model, vs, 3, engine_kwargs=dict(max_slots=2, block_size=4),
        replica_mode="socket", roles=["prefill", "decode", "decode"],
        clock=SimClock(), heartbeat_timeout_s=0.25, est_tick_s=0.1,
        transport_timeout_s=10.0,
        root=tempfile.mkdtemp(prefix="paddle_tpu_fleet_sock_"))
    rng5 = np.random.RandomState(5)
    try:
        frs5 = [sock_fleet.submit(list(rng5.randint(1, V, int(p))), 5)
                for p in rng5.randint(2, 8, 6)]
        for _ in range(300):
            if not sock_fleet.outstanding():
                break
            sock_fleet.tick()
            sock_fleet.clock.advance(0.1)
        stats5 = sock_fleet.stats()
        sock_terminal = all(fr.record is not None for fr in frs5)
        sock_oracle = all(
            fr.finish_reason == "length"
            and fr.tokens == greedy_oracle(fr.prompt, fr.max_new_tokens)
            for fr in frs5)
        sock_roles = all(fr.attempts[0] == 0 and fr.replica in (1, 2)
                         for fr in frs5)
        sock_wire_exact = (
            stats5["handoffs"] == len(frs5)
            and stats5["handoff_wire_bytes"]
            == stats5["handoff_blocks"] * f32_block)
    finally:
        sock_fleet.shutdown()

    # 5b: decode isolation under prefill load — the disaggregation
    # claim, measured. The same decode jobs run twice on in-process
    # role fleets; run B adds heavy prefill-only jobs (long prompts,
    # max_new=1 finishes at prefill, no handoff). Decode throughput —
    # ticks until the decode jobs all finish — must hold within 25%.
    def run_disagg(extra_prefill, kv_dtype=None):
        ek = dict(max_slots=2, block_size=4)
        if kv_dtype:
            ek["kv_dtype"] = kv_dtype
        f5 = ServingFleet.from_model(
            model, vs, 3, engine_kwargs=ek,
            roles=["prefill", "decode", "decode"], clock=SimClock(),
            heartbeat_timeout_s=0.25, est_tick_s=0.1,
            root=tempfile.mkdtemp(prefix="paddle_tpu_fleet_disagg_"))
        r = np.random.RandomState(9)
        decode_jobs = [f5.submit(list(r.randint(1, V, 4)), 6)
                       for _ in range(6)]
        if extra_prefill:
            for _ in range(8):
                f5.submit(list(r.randint(1, V, 20)), 1)
        done_at = None
        for _ in range(400):
            if done_at is None and all(fr.record is not None
                                       for fr in decode_jobs):
                done_at = f5.ticks
            if not f5.outstanding():
                break
            f5.tick()
            f5.clock.advance(0.1)
        if done_at is None and all(fr.record is not None
                                   for fr in decode_jobs):
            done_at = f5.ticks
        st = f5.stats()
        toks = sum(len(fr.tokens) for fr in decode_jobs)
        return {"fleet": f5, "stats": st, "decode_jobs": decode_jobs,
                "decode_done_tick": done_at,
                "decode_tok_per_tick": (toks / done_at
                                        if done_at else None)}

    base = run_disagg(extra_prefill=False)
    loaded = run_disagg(extra_prefill=True)
    iso_ratio = (loaded["decode_tok_per_tick"]
                 / base["decode_tok_per_tick"]
                 if base["decode_tok_per_tick"]
                 and loaded["decode_tok_per_tick"] else None)
    iso_ok = (iso_ratio is not None and iso_ratio >= 0.75
              and all(fr.tokens == base["decode_jobs"][i].tokens
                      for i, fr in enumerate(loaded["decode_jobs"])))

    # 5c: int8 KV crosses the wire quantized — identical tokens to the
    # colocated int8 fleet, ~2.7x fewer bytes per block than f32
    q5 = run_disagg(extra_prefill=False, kv_dtype="int8")
    colo5 = ServingFleet.from_model(
        model, vs, 2,
        engine_kwargs=dict(max_slots=2, block_size=4, kv_dtype="int8"),
        clock=SimClock(), heartbeat_timeout_s=0.25, est_tick_s=0.1,
        root=tempfile.mkdtemp(prefix="paddle_tpu_fleet_colo8_"))
    rq = np.random.RandomState(9)
    colo_jobs = [colo5.submit(list(rq.randint(1, V, 4)), 6)
                 for _ in range(6)]
    for _ in range(400):
        if not colo5.outstanding():
            break
        colo5.tick()
        colo5.clock.advance(0.1)
    q_stats = q5["stats"]
    quant_identical = all(
        a.tokens == b.tokens and a.finish_reason == b.finish_reason
        for a, b in zip(colo_jobs, q5["decode_jobs"]))
    q_wire_exact = (q_stats["handoffs"] >= 6
                    and q_stats["handoff_wire_bytes"]
                    == q_stats["handoff_blocks"] * int8_block)
    quant_wire_ratio = f32_block / int8_block    # 2.67x for hd=8
    disagg = {
        "ok": bool(sock_terminal and sock_oracle and sock_roles
                   and sock_wire_exact and iso_ok and quant_identical
                   and q_wire_exact
                   and stats5["router_ms"]["total"] > 0.0),
        "socket_all_terminal": bool(sock_terminal),
        "socket_oracle_tokens": bool(sock_oracle),
        "socket_role_placement": bool(sock_roles),
        "socket_wire_bytes_exact": bool(sock_wire_exact),
        "socket_handoffs": stats5["handoffs"],
        "socket_wire_bytes": stats5["handoff_wire_bytes"],
        "router_ms": stats5["router_ms"],
        "decode_tok_per_tick_base": base["decode_tok_per_tick"],
        "decode_tok_per_tick_loaded": loaded["decode_tok_per_tick"],
        "decode_isolation_ratio": iso_ratio,
        "decode_isolated_under_prefill_load": bool(iso_ok),
        "int8_tokens_identical_to_colocated": bool(quant_identical),
        "int8_wire_bytes_exact": bool(q_wire_exact),
        "int8_wire_ratio_vs_f32": quant_wire_ratio,
    }

    # -- leg 6: partition + flap chaos gate (ISSUE 20). The leg-5a
    # disagg socket fleet re-run under a seeded NetworkChaos plane:
    # link 0 (the only prefill) loses its REPLY direction for two fleet
    # seconds — the asymmetric partition: the child hears every frame,
    # the parent hears nothing — which manufactures a false death,
    # an epoch fence, and the disagg→colocated degradation; link 2
    # takes a single flap window that drops one tick exchange outright
    # and fences a decode replica the same way. Both zombies must be
    # re-admitted on heal having generated ZERO tokens under their
    # fenced epochs, every rid must keep exactly one terminal record
    # with oracle tokens, and the chaos-off leg-5a fleet is the dark
    # twin: same stats schema plus exactly the "chaos" ledger.
    from paddle_tpu.serve import LinkChaos, NetworkChaos
    chaos_plane = NetworkChaos(20, links={
        0: LinkChaos(partitions=[(0.25, 2.5, "recv")]),
        2: LinkChaos(flap=(50.0, 0.12, 0.9))})
    mem6 = InMemorySink()
    fleet6 = ServingFleet.from_model(
        model, vs, 3, engine_kwargs=dict(max_slots=2, block_size=4),
        replica_mode="socket", roles=["prefill", "decode", "decode"],
        chaos=chaos_plane, clock=SimClock(),
        heartbeat_timeout_s=0.25, est_tick_s=0.1, warmup=True,
        transport_timeout_s=0.75, readmit_grace_s=100.0,
        telemetry=Telemetry(sinks=[mem6]),
        root=tempfile.mkdtemp(prefix="paddle_tpu_fleet_chaos_"))
    rng6 = np.random.RandomState(6)
    try:
        frs6 = [fleet6.submit(list(rng6.randint(1, V, int(p))), 8)
                for p in rng6.randint(2, 8, 6)]
        late6 = []
        for _ in range(400):
            if not late6 and fleet6.clock() >= 1.5:
                # mid-degradation arrivals: routed straight to the
                # colocated decode path, no prefill replica alive
                late6 = [fleet6.submit(list(rng6.randint(1, V, 4)), 6)
                         for _ in range(2)]
            if (not fleet6.outstanding()
                    and fleet6.readmitted >= fleet6.fences
                    and not fleet6.degraded):
                break
            fleet6.tick()
            fleet6.clock.advance(0.1)
        frs6 += late6
        stats6 = fleet6.stats()
        mb6 = stats6["membership"]
        ch6 = stats6["chaos"]
        chaos_terminal = all(fr.record is not None for fr in frs6)
        chaos_oracle = all(
            fr.finish_reason == "length"
            and fr.tokens == greedy_oracle(fr.prompt, fr.max_new_tokens)
            for fr in frs6)
        term6 = collections.Counter(
            r["rid"] for r in mem6.by_kind("request")
            if r["finish_reason"] != "retried")
        chaos_lineage = (set(term6) == {fr.rid for fr in frs6}
                         and all(v == 1 for v in term6.values()))
        fenced6 = [w for w in fleet6.workers if w.readmit_info]
        zero_zombie_tokens = (
            len(fenced6) == fleet6.fences
            and all(w.readmit_info["tokens_while_fenced"] == 0
                    for w in fenced6))
        live6 = [w for w in fleet6.workers if w.state == "live"]
        chaos_no_leak = (len(live6) == 3 and all(
            w.engine.free_blocks == w.engine.num_blocks - 1
            for w in live6))
        degrade_cycle = (mb6["degradations"] >= 1
                         and mb6["degrade_releases"] >= 1
                         and not mb6["degraded"])
        chaos_evidence = (
            ch6["frames_dropped"] > 0
            and ch6["drop_reasons"].get("partition", 0) > 0
            and ch6["drop_reasons"].get("flap", 0) > 0)
        dark_twin_keys = set(stats6) - set(stats5) == {"chaos"}
    finally:
        fleet6.shutdown()
    chaos6 = {
        "ok": bool(chaos_terminal and chaos_oracle and chaos_lineage
                   and zero_zombie_tokens and chaos_no_leak
                   and degrade_cycle and chaos_evidence
                   and dark_twin_keys and fleet6.fences >= 2
                   and fleet6.readmitted >= fleet6.fences),
        "all_terminal": bool(chaos_terminal),
        "oracle_tokens": bool(chaos_oracle),
        "single_lineage": bool(chaos_lineage),
        "fences": fleet6.fences,
        "readmitted": fleet6.readmitted,
        "zero_tokens_while_fenced": bool(zero_zombie_tokens),
        "survivors_leak_free": bool(chaos_no_leak),
        "degradation_engaged_and_released": bool(degrade_cycle),
        "membership": mb6,
        "network": ch6,
        "stats_keys_vs_dark_twin": sorted(set(stats6) - set(stats5)),
    }

    inproc_ok = (all_terminal and lineage_ok and no_leak and no_retrace
                 and p99_finite and shed_bounded and stats["resubmits"] >= 1
                 and stats["stale_completions"] == 0 and sjf_wins)
    ok = (inproc_ok and proc["ok"] and tracing["ok"] and disagg["ok"]
          and chaos6["ok"])
    print(json.dumps({
        "child": "fleet", "ok": bool(ok),
        "workload": workload_stats(wl),
        "inprocess": {
            "ok": bool(inproc_ok),
            "all_terminal": bool(all_terminal),
            "lineage_ok": bool(lineage_ok),
            "no_leak_on_survivors": bool(no_leak),
            "zero_retraces_on_survivors": bool(no_retrace),
            "p99_ttft_finite": bool(p99_finite),
            "shed_bounded": bool(shed_bounded),
            "sjf_beats_fcfs_goodput": bool(sjf_wins),
            "goodput_fcfs_pct": fcfs["goodput_pct"],
            "goodput_sjf_pct": sjf["goodput_pct"],
            "stats": stats, "requests": summary,
            "faults_fired": [p for p, _ in faults.fired]},
        "process": proc,
        "tracing": tracing,
        "disagg": disagg,
        "chaos": chaos6,
        "device": jax.devices()[0].device_kind,
    }))
    return 0 if ok else 1


# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# cold-against-warm replica spawn (ISSUE 16)
# ---------------------------------------------------------------------------

def _replica_spawn_once(spec, replica_id, prompt, new_tokens, env):
    """Spawn ONE fresh replica child against ``spec``, drive a single
    request to completion over the stdio transport, and return its
    tokens, the child's own ``startup_ms`` record, and its compile
    counts at hello and after the traffic."""
    from paddle_tpu.serve import transport as tp
    proc = tp.spawn_replica_process(dict(spec, replica_id=replica_id),
                                    stderr=subprocess.DEVNULL, env=env)
    trans = tp.ReplicaTransport(proc.stdout, proc.stdin, proc=proc,
                                timeout_s=300.0)
    try:
        hello = trans.request("hello", now=0.0, timeout_s=300.0)
        trans.request("submit", rid=1, prompt=list(prompt),
                      max_new_tokens=new_tokens, now=0.0)
        tokens, load = None, {}
        for i in range(16 + 4 * new_tokens):
            rep = trans.request("tick", now=0.05 * (i + 1), timeout_s=120.0)
            load = rep.get("load") or load
            if rep.get("completed"):
                tokens = rep["completed"][0]["tokens"]
                break
        trans.request("stop", now=9.0)
    finally:
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
    return {"tokens": tokens, "startup_ms": hello.get("startup_ms") or {},
            "hello_compile_counts": (hello.get("load") or {}).get(
                "compile_counts"),
            "final_compile_counts": load.get("compile_counts")}


def run_spawn_child():
    """Cold-against-warm spawn drill (ISSUE 16; tiny config): two fresh
    replica processes, one after the other, share one cache directory.
    The cold one runs >= 1 autotune trial and misses both persistent
    caches; the warm one runs ZERO trials, hits both and adds no entry;
    both keep ``compile_counts == {prefill: 1, tick: 1}`` through real
    traffic (warmup adds no variants), and the two emit identical tokens
    (warmup and caches are semantically invisible).

    THIS process only builds the weights and the spec, and pins itself to
    the CPU before it touches JAX: a parent that holds a chip would starve
    its own children. The XLA cache directory is placed through the
    children's environment (``JAX_COMPILATION_CACHE_DIR``): a fixed path
    under this process's own cache directory, emptied first so that the
    first spawn is cold. Prints the verdict as one JSON line; exit 0 iff
    every check holds."""
    import shutil
    import tempfile
    jax.config.update("jax_platforms", "cpu")
    from paddle_tpu.models import TransformerLM
    from paddle_tpu.serve import fleet as fleet_lib

    vocab, max_len, new_tokens = 64, 64, 2
    model = TransformerLM(vocab=vocab, dim=32, num_layers=1, num_heads=2,
                          ffn_hidden=128, max_len=max_len)
    vs = model.init(jax.random.PRNGKey(0),
                    jnp.zeros((1, max_len), jnp.int32))
    root = tempfile.mkdtemp(prefix="paddle_tpu_replica_spawn_")
    cache_dir = os.path.join(xla_cache.setup(), "spawn_drill")
    shutil.rmtree(cache_dir, ignore_errors=True)
    spec = fleet_lib.build_proc_spec(
        model, vs, root,
        engine_kwargs=dict(max_slots=2, block_size=4),
        warmup=True,
        autotune_cache_dir=os.path.join(root, "autotune"))
    env = dict(os.environ, **{xla_cache.ENV_VAR: cache_dir})
    rng = np.random.RandomState(0)
    prompt = list(rng.randint(2, vocab, 4))
    cold = _replica_spawn_once(spec, 0, prompt, new_tokens, env)
    warm = _replica_spawn_once(spec, 1, prompt, new_tokens, env)
    su_c, su_w = cold["startup_ms"], warm["startup_ms"]
    pinned = {"prefill": 1, "tick": 1}
    checks = {
        "cold_tuned": (su_c.get("autotune_trials") or 0) >= 1,
        "cold_autotune_miss": su_c.get("autotune_cache_hit") is False,
        "cold_xla_miss": su_c.get("xla_cache_hit") is False,
        "warm_zero_trials": su_w.get("autotune_trials") == 0,
        "warm_autotune_hit": su_w.get("autotune_cache_hit") is True,
        "warm_xla_hit": su_w.get("xla_cache_hit") is True,
        "warm_adds_no_cache_entry":
            su_w.get("xla_cache_entries_added") == 0,
        "token_identical": cold["tokens"] is not None
        and cold["tokens"] == warm["tokens"],
        "compile_counts_pinned":
            cold["final_compile_counts"] == pinned
            and warm["final_compile_counts"] == pinned
            and warm["hello_compile_counts"] == pinned,
    }
    ok = all(checks.values())
    print(json.dumps({
        "child": "spawn", "ok": bool(ok),
        "warm_start": {"ok": bool(ok), **checks},
        "cold_startup_ms": su_c, "warm_startup_ms": su_w,
    }))
    return 0 if ok else 1


DRILLS = {"serving": run_serving_child, "faults": run_faults_child,
          "fleet": run_fleet_child, "spawn": run_spawn_child}


def main():
    if len(sys.argv) != 2 or sys.argv[1] not in DRILLS:
        sys.exit(f"usage: drills.py <{'|'.join(DRILLS)}>")
    xla_cache.setup()
    sys.exit(DRILLS[sys.argv[1]]())


if __name__ == "__main__":
    main()
