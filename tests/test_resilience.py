"""Elastic fault tolerance (ISSUE 10): the kill-anywhere property, the
checkpoint fallback chain, preemption, heartbeats, and the
zero-overhead-when-off pin.

The acceptance contract: for a seeded fault-schedule sweep (crash
before/during/after save, preemption mid-pass, corrupt latest pass,
stager producer error) the SUPERVISED run completes and its final params
are BIT-EQUAL (f32) to the uninterrupted run — recovery is not
"approximately resumes", it is the same training trajectory. And with
``faults=None``, no supervisor, no heartbeat, the Trainer is the exact
pre-PR hot loop (dispatch count, fences, params)."""

import glob
import os
import signal
import time

import numpy as np
import jax
import pytest

from paddle_tpu import data, optim
from paddle_tpu.models import MnistMLP
from paddle_tpu.nn import costs
from paddle_tpu.parallel import multihost
from paddle_tpu.train import (FaultSchedule, InjectedCrash, Preempted,
                              SupervisorGaveUp, Trainer, checkpoint as ckpt,
                              faults as faults_lib, resilience,
                              run_resilient)

BS, N_BATCHES = 8, 16


def make_batches(n=N_BATCHES, bs=BS, seed=0):
    rng = np.random.RandomState(seed)
    return [{"x": rng.rand(bs, 784).astype(np.float32),
             "label": rng.randint(0, 10, (bs,)).astype(np.int32)}
            for _ in range(n)]


BATCHES = make_batches()


def reader():
    return iter(BATCHES)


def make_trainer(faults=None, **kw):
    tr = Trainer(
        model=MnistMLP(),
        loss_fn=lambda out, b: costs.softmax_cross_entropy(out, b["label"]),
        optimizer=optim.adam(1e-3), faults=faults, **kw)
    tr.init(jax.random.PRNGKey(0), BATCHES[0])
    return tr


def params_of(state):
    return jax.tree_util.tree_leaves(jax.device_get(state.params))


def assert_params_equal(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def baseline(tmp_path, num_passes=2, saving_period=4, **kw):
    tr = make_trainer(**kw)
    tr.train(reader, num_passes=num_passes,
             checkpoint_dir=str(tmp_path / "baseline"),
             saving_period=saving_period, log_period=0)
    return params_of(tr.train_state)


# ---------------------------------------------------------------------------
# the kill-anywhere sweep (acceptance criterion)
# ---------------------------------------------------------------------------

# (name, FaultSchedule kwargs, extra Trainer kwargs). All with
# steps_per_call=2 over 16 batches x 2 passes (M=1: 16 optimizer steps
# per pass; saving_period=4: boundary saves at batches 4/8/12/16, plus
# the pass-end save — save indices 0..4 in pass 0, 5..9 in pass 1).
SWEEP = [
    # crash before ANY save lands (step 1, first group): resume finds no
    # checkpoint and replays from scratch
    ("crash_before_save", dict(crash_at_step=1), {}),
    # crash right after the batch-4 boundary save: resume mid-pass
    ("crash_after_save", dict(crash_at_step=5), {}),
    # crash INSIDE the save path (the write never lands): transient I/O,
    # retry resumes from the previous checkpoint
    ("crash_during_save", dict(fail_save_at=1), {}),
    # the latest landed checkpoint (pass-0 end, save idx 4) is corrupted,
    # then a crash early in pass 1: resume quarantines the poisoned pass
    # and falls back (here: to scratch — test_drills.py's faults-corrupt
    # leg covers the fall-back-one-PASS case with 3 passes)
    ("corrupt_latest_pass",
     dict(corrupt_checkpoint_file=4, crash_at_step=18), {}),
    # preemption notice mid-pass: graceful stop -> quiesced checkpoint ->
    # distinct status -> a second supervised run resumes
    ("preempt_mid_pass", dict(preempt_at_step=5), {}),
    # the stager thread dies staging a group (producer-error propagation
    # through the host pipeline): supervisor retries with resume
    ("stager_error", dict(stager_error_at_group=4),
     {"pipeline_depth": 2}),
]


@pytest.mark.parametrize("name,fs_kw,tr_kw",
                         SWEEP, ids=[s[0] for s in SWEEP])
def test_kill_anywhere_bit_equal(tmp_path, name, fs_kw, tr_kw):
    p0 = baseline(tmp_path, steps_per_call=2, **tr_kw)
    ck = str(tmp_path / "supervised")
    # ONE schedule instance across attempts: the one-shot disarm is what
    # makes the injected fault transient
    fs = FaultSchedule(**fs_kw)
    res = run_resilient(
        lambda: make_trainer(faults=fs, steps_per_call=2, **tr_kw),
        reader, checkpoint_dir=ck, num_passes=2, saving_period=4,
        log_period=0, backoff_s=0.001)
    if res.status == "preempted":
        # the preempt checkpoint recorded the quiesced mid-pass position
        assert res.preempted is not None
        it = ckpt.load_checkpoint(ck)["iter"]
        assert int(it["preempted"]) == 1 and int(it["completed"]) == 0
        res = run_resilient(
            lambda: make_trainer(steps_per_call=2, **tr_kw),
            reader, checkpoint_dir=ck, num_passes=2, saving_period=4,
            log_period=0, backoff_s=0.001)
    assert res.status == "completed", (name, res)
    assert fs.fired, name                 # the fault really fired
    assert_params_equal(p0, params_of(res.state))
    if name == "corrupt_latest_pass":
        assert res.fallbacks, res
        assert glob.glob(os.path.join(ck, "*.corrupt*"))


def test_supervisor_gives_up_on_deterministic_failure(tmp_path):
    """A failure recurring at the same step (fresh schedule each attempt,
    no checkpoint to skip past it) is deterministic — give up loud with
    the attempts ledger, don't burn the restart budget."""
    with pytest.raises(SupervisorGaveUp, match="recurred"):
        run_resilient(
            lambda: make_trainer(faults=FaultSchedule(crash_at_step=2),
                                 steps_per_call=2),
            reader, checkpoint_dir=str(tmp_path / "ck"), num_passes=1,
            log_period=0, backoff_s=0.001, same_step_limit=3,
            max_restarts=10)


def test_supervisor_restart_budget(tmp_path):
    """Distinct failures past max_restarts also give up (chained)."""
    calls = {"n": 0}

    def flaky_reader():
        calls["n"] += 1
        raise OSError(f"flaky transport #{calls['n']}")

    with pytest.raises(SupervisorGaveUp, match="budget"):
        run_resilient(
            lambda: make_trainer(steps_per_call=2), flaky_reader,
            checkpoint_dir=str(tmp_path / "ck"), num_passes=1,
            log_period=0, backoff_s=0.001, max_restarts=2,
            same_step_limit=99)


def test_nan_is_fatal_not_retried(tmp_path):
    """nan_check's FloatingPointError re-raises immediately: a restart
    replays the same batches into the same NaN."""
    bad = [{"x": np.full((BS, 784), np.nan, np.float32),
            "label": np.zeros((BS,), np.int32)}]
    attempts = {"n": 0}

    def make():
        attempts["n"] += 1
        return make_trainer(nan_check=True)

    with pytest.raises(FloatingPointError):
        run_resilient(make, lambda: iter(bad),
                      checkpoint_dir=str(tmp_path / "ck"), num_passes=1,
                      log_period=0, backoff_s=0.001)
    assert attempts["n"] == 1             # no retry


# ---------------------------------------------------------------------------
# zero-overhead-when-off pin (PR-2/4/6 style)
# ---------------------------------------------------------------------------

def _count_dispatches(tr):
    calls = {"n": 0}
    orig = tr._dispatch_fused

    def counting(stacked, rng, **kw):
        calls["n"] += 1
        return orig(stacked, rng, **kw)

    tr._dispatch_fused = counting
    tr.train(reader, num_passes=1, log_period=0)
    return calls["n"]


def test_faults_off_zero_overhead(monkeypatch):
    """faults=None, no supervisor, no heartbeat: same dispatch count,
    zero fences, bit-identical params vs an attached-but-empty schedule
    — the injection plane costs nothing when disarmed and nothing is
    traced into the step either way."""
    fences = {"n": 0}
    orig_fence = jax.block_until_ready

    def counting_fence(x):
        fences["n"] += 1
        return orig_fence(x)

    monkeypatch.setattr(jax, "block_until_ready", counting_fence)

    tr_off = make_trainer(steps_per_call=2)
    n_off = _count_dispatches(tr_off)
    assert fences["n"] == 0

    tr_empty = make_trainer(faults=FaultSchedule(), steps_per_call=2)
    n_empty = _count_dispatches(tr_empty)
    assert n_empty == n_off
    assert fences["n"] == 0               # still no fence either way
    assert_params_equal(params_of(tr_off.train_state),
                        params_of(tr_empty.train_state))


def test_fault_points_are_one_shot():
    fs = FaultSchedule(crash_at_step=2)
    with pytest.raises(InjectedCrash):
        fs.maybe_crash_step(2)
    fs.maybe_crash_step(2)                # disarmed: no raise
    assert fs.fired == [("crash_at_step", 2)]
    fs2 = FaultSchedule(preempt_at_step=4)
    assert fs2.should_preempt(4) is True
    assert fs2.should_preempt(4) is False


# ---------------------------------------------------------------------------
# checkpoint fallback chain + resume seams
# ---------------------------------------------------------------------------

def _save(root, pass_id, val):
    ckpt.save_checkpoint(str(root), pass_id,
                         {"params": {"w": np.full((4,), float(val))}})


def test_load_latest_valid_quarantines_and_falls_back(tmp_path, caplog):
    _save(tmp_path, 0, 1.0)
    _save(tmp_path, 1, 2.0)
    corrupted = faults_lib.corrupt_one_file(
        os.path.join(str(tmp_path), "pass-00001"))
    assert corrupted is not None
    with caplog.at_level("WARNING"):
        out = ckpt.load_latest_valid(str(tmp_path))
    assert out["pass_id"] == 0
    np.testing.assert_allclose(out["params"]["w"], np.ones((4,)))
    # quarantined, never deleted: the bytes are still on disk
    q = os.path.join(str(tmp_path), "pass-00001.corrupt")
    assert out["_quarantined"] == [q]
    assert os.path.isdir(q)
    assert not os.path.exists(os.path.join(str(tmp_path), "pass-00001"))
    assert any("quarantined" in r.message for r in caplog.records)


def test_fallback_prefers_readable_sibling_of_same_pass(tmp_path):
    """A corrupt live dir with a complete .old crash leftover falls back
    WITHIN the pass first: quarantine the live dir, read the .old."""
    root = str(tmp_path / "root")
    side = str(tmp_path / "side")
    ckpt._write_pass_dir(root, 0, {"params": {"w": np.full((2,), 2.0)}})
    # a crash leftover from the v1 save era (built aside: the live
    # writer's swap garbage-collects true .old siblings on success)
    ckpt._write_pass_dir(side, 0, {"params": {"w": np.full((2,), 1.0)}})
    os.rename(os.path.join(side, "pass-00000"),
              os.path.join(root, "pass-00000.old"))
    faults_lib.corrupt_one_file(os.path.join(root, "pass-00000"))
    out = ckpt.load_latest_valid(root)
    assert out["pass_id"] == 0
    np.testing.assert_allclose(out["params"]["w"], np.full((2,), 1.0))
    assert os.path.isdir(os.path.join(root, "pass-00000.corrupt"))


def test_all_corrupt_raises_with_ledger(tmp_path):
    _save(tmp_path, 0, 1.0)
    faults_lib.corrupt_one_file(os.path.join(str(tmp_path), "pass-00000"))
    with pytest.raises(FileNotFoundError) as ei:
        ckpt.load_latest_valid(str(tmp_path))
    assert len(ei.value.quarantined) == 1
    assert os.path.isdir(ei.value.quarantined[0])


def test_corrupt_dirs_invisible_to_latest_resolve_and_gc(tmp_path):
    root = str(tmp_path)
    for i in range(3):
        _save(tmp_path, i, float(i))
    q = ckpt.quarantine_pass_dir(os.path.join(root, "pass-00002"))
    assert ckpt.latest_pass(root) == 1
    assert ckpt._base_pass_id(os.path.basename(q)) is None
    ckpt._gc(root, keep_last=1)
    left = sorted(d for d in os.listdir(root) if d.startswith("pass-"))
    # retention pruned pass-0, kept pass-1, left the quarantine alone
    assert left == ["pass-00001", "pass-00002.corrupt"]


def test_quarantine_name_collisions_get_suffixes(tmp_path):
    root = str(tmp_path)
    for _ in range(2):
        _save(tmp_path, 0, 1.0)
        ckpt.quarantine_pass_dir(os.path.join(root, "pass-00000"))
    names = sorted(os.listdir(root))
    assert names == ["pass-00000.corrupt", "pass-00000.corrupt2"]


def test_resume_starts_fresh_when_nothing_readable(tmp_path, caplog):
    """Trainer(resume=True) over an all-corrupt checkpoint dir warns and
    trains from scratch — bit-equal to a clean run — instead of dying."""
    p0 = baseline(tmp_path, num_passes=1, saving_period=None)
    ck = str(tmp_path / "ck")
    tr = make_trainer()
    tr.train(reader, num_passes=1, checkpoint_dir=ck, log_period=0)
    faults_lib.corrupt_one_file(os.path.join(ck, "pass-00000"))
    tr2 = make_trainer()
    with caplog.at_level("WARNING"):
        tr2.train(reader, num_passes=1, checkpoint_dir=ck, resume=True,
                  log_period=0)
    assert any("starting from scratch" in r.message for r in caplog.records)
    assert tr2.last_quarantined                  # the ledger survived
    assert_params_equal(p0, params_of(tr2.train_state))


def test_vanished_dir_mid_read_rescans_not_raises(tmp_path, monkeypatch):
    """Multi-reader race: another host quarantines (renames away) the
    pass dir between our latest_pass probe and the load — we must
    RE-SCAN and converge on the same fallback pass, not die or restart
    from scratch on the other host's rename."""
    import shutil
    _save(tmp_path, 0, 1.0)
    _save(tmp_path, 1, 2.0)
    real_load = ckpt.load_checkpoint
    raced = {"n": 0}

    def racing_load(root, pass_id=None, **kw):
        if pass_id == 1 and raced["n"] == 0:
            raced["n"] += 1
            # the "other host" moved it away mid-read
            shutil.move(os.path.join(root, "pass-00001"),
                        os.path.join(root, "pass-00001.corrupt"))
            raise FileNotFoundError("vanished under concurrent rename")
        return real_load(root, pass_id, **kw)

    monkeypatch.setattr(ckpt, "load_checkpoint", racing_load)
    out = ckpt.load_latest_valid(str(tmp_path))
    assert out["pass_id"] == 0 and raced["n"] == 1
    assert out["_quarantined"] == []          # we didn't quarantine it


def test_stop_request_scoped_to_one_train_call(tmp_path):
    """A consumed (or stale) stop request must not instantly re-preempt
    the next train() on the same instance — zero-forward-progress loop
    otherwise."""
    ck = str(tmp_path / "ck")
    tr = make_trainer()

    def handler(e):
        from paddle_tpu.train import events as ev
        if isinstance(e, ev.EndIteration) and e.batch_id == 1 \
                and e.pass_id == 0:
            tr.request_stop("once")

    with pytest.raises(Preempted):
        tr.train(reader, num_passes=1, checkpoint_dir=ck, log_period=0,
                 event_handler=handler)
    # same instance, no new request: must run to completion
    state = tr.train(reader, num_passes=1, checkpoint_dir=ck,
                     resume=True, log_period=0)
    assert state is tr.train_state
    it = ckpt.load_checkpoint(ck)["iter"]
    assert int(it["completed"]) == 1


def test_preempt_checkpoint_carries_batch_crc(tmp_path):
    """The preempt save records the last consumed batch's fingerprint —
    the resume-time nondeterministic-reader check guards the elastic
    path like every saving_period save."""
    ck = str(tmp_path / "ck")
    tr = make_trainer(steps_per_call=2)
    fs = FaultSchedule(preempt_at_step=5)
    tr.faults = fs
    with pytest.raises(Preempted) as ei:
        tr.train(reader, num_passes=1, checkpoint_dir=ck, log_period=0)
    it = ckpt.load_checkpoint(ck)["iter"]
    nb = ei.value.next_batch
    from paddle_tpu.train.trainer import _batch_fingerprint
    assert int(it["batch_crc"]) == _batch_fingerprint(BATCHES[nb - 1])


def test_detect_dead_hosts_uses_mtime_in_production(tmp_path):
    """Production staleness is the heartbeat FILE's mtime (one clock
    pair per reader), so a live host with a skewed wall clock is never
    declared dead — and a genuinely stale file is, whatever its payload
    claims."""
    root = str(tmp_path)
    # host 0: beating now, but its wall clock is an hour behind
    multihost.write_heartbeat(root, host_id=0, now=time.time() - 3600)
    # host 1: payload claims "now", but the file is actually old
    p = multihost.write_heartbeat(root, host_id=1, now=time.time())
    os.utime(p, (time.time() - 3600, time.time() - 3600))
    assert multihost.detect_dead_hosts(root, timeout_s=60.0) == [1]


def test_explicit_pass_id_restore_stays_strict(tmp_path):
    """restore(dir, pass_id) keeps the hard-raise contract — only the
    latest-valid path (pass_id=None) quarantines."""
    ck = str(tmp_path / "ck")
    tr = make_trainer()
    tr.train(reader, num_passes=1, checkpoint_dir=ck, log_period=0)
    faults_lib.corrupt_one_file(os.path.join(ck, "pass-00000"))
    with pytest.raises(ckpt.CorruptCheckpointError):
        make_trainer().restore(ck, 0)
    assert os.path.isdir(os.path.join(ck, "pass-00000"))  # untouched


def test_resolve_crash_leftovers_under_quarantine(tmp_path):
    """The kill-between-the-two-renames leftovers (.tmp newer than
    .old) still resolve after the newer one is quarantined."""
    root = str(tmp_path / "root")
    side = str(tmp_path / "side")
    os.makedirs(root)
    ckpt._write_pass_dir(side, 0, {"params": {"w": np.full((2,), 1.0)}})
    os.rename(os.path.join(side, "pass-00000"),
              os.path.join(root, "pass-00000.old"))
    ckpt._write_pass_dir(side, 0, {"params": {"w": np.full((2,), 2.0)}})
    os.rename(os.path.join(side, "pass-00000"),
              os.path.join(root, "pass-00000.tmp"))
    # live missing: .tmp (newer) resolves first
    assert ckpt._resolve_pass_dir(root, 0).endswith(".tmp")
    faults_lib.corrupt_one_file(os.path.join(root, "pass-00000.tmp"))
    out = ckpt.load_latest_valid(root)
    np.testing.assert_allclose(out["params"]["w"], np.full((2,), 1.0))
    assert os.path.isdir(os.path.join(root, "pass-00000.tmp.corrupt"))


# ---------------------------------------------------------------------------
# preemption: request_stop / SIGTERM
# ---------------------------------------------------------------------------

def test_request_stop_quiesces_and_resume_is_bit_equal(tmp_path):
    """A stop requested mid-pass (the signal handler's effect) drains,
    writes a quiesced mid-pass checkpoint, raises Preempted with the
    exact iterator position — and the resumed run is bit-equal."""
    p0 = baseline(tmp_path, num_passes=2, saving_period=None)
    ck = str(tmp_path / "ck")
    tr = make_trainer()

    def handler(e):
        from paddle_tpu.train import events as ev
        if isinstance(e, ev.EndIteration) and e.batch_id == 2 \
                and e.pass_id == 0:
            tr.request_stop("test")

    with pytest.raises(Preempted) as ei:
        tr.train(reader, num_passes=2, checkpoint_dir=ck, log_period=0,
                 event_handler=handler)
    assert ei.value.pass_id == 0 and ei.value.next_batch == 3
    it = ckpt.load_checkpoint(ck)["iter"]
    assert int(it["next_batch"]) == 3 and int(it["preempted"]) == 1
    tr2 = make_trainer()
    tr2.train(reader, num_passes=2, checkpoint_dir=ck, resume=True,
              log_period=0)
    assert_params_equal(p0, params_of(tr2.train_state))


def test_sigterm_handler_requests_stop():
    tr = make_trainer()
    restore = resilience.install_preemption_handler(tr)
    try:
        os.kill(os.getpid(), signal.SIGTERM)
        deadline = time.time() + 5
        while tr._stop_requested is None and time.time() < deadline:
            time.sleep(0.01)              # the delivery checkpoint
        assert tr._stop_requested is not None
        assert "signal" in tr._stop_requested
    finally:
        restore()


# ---------------------------------------------------------------------------
# producer error landing on a checkpoint drain boundary (resume seam)
# ---------------------------------------------------------------------------

def test_buffered_producer_error_at_drain_boundary(tmp_path):
    """A data.buffered fill-thread failure that lands exactly on the
    saving_period drain boundary surfaces promptly (no hang, stager
    closed), the boundary checkpoint is intact, and the supervised retry
    finishes bit-equal."""
    p0 = baseline(tmp_path, steps_per_call=2, pipeline_depth=2)
    failures = {"n": 0}

    def flaky_source():
        for i, b in enumerate(BATCHES):
            if i == 8 and failures["n"] == 0:     # exactly the boundary
                failures["n"] += 1
                raise ValueError("injected producer failure at boundary")
            yield b

    flaky_reader = data.buffered(lambda: flaky_source(), size=2)
    ck = str(tmp_path / "ck")
    res = run_resilient(
        lambda: make_trainer(steps_per_call=2, pipeline_depth=2),
        flaky_reader, checkpoint_dir=ck, num_passes=2, saving_period=4,
        log_period=0, backoff_s=0.001)
    assert res.status == "completed" and res.restarts == 1
    assert failures["n"] == 1
    assert_params_equal(p0, params_of(res.state))


# ---------------------------------------------------------------------------
# heartbeats, dead-host detection, reformed-mesh restart
# ---------------------------------------------------------------------------

def test_heartbeat_write_read_detect(tmp_path):
    root = str(tmp_path)
    multihost.write_heartbeat(root, host_id=0, seq=1, now=100.0)
    multihost.write_heartbeat(root, host_id=1, seq=1, now=100.0)
    multihost.write_heartbeat(root, host_id=2, seq=1, now=40.0)  # stale
    beats = multihost.read_heartbeats(root)
    assert sorted(beats) == [0, 1, 2]
    assert beats[0]["pid"] == os.getpid() and beats[0]["seq"] == 1
    # host 2 is stale; host 3 never joined (only dead when expected)
    assert multihost.detect_dead_hosts(root, timeout_s=30.0,
                                       now=110.0) == [2]
    assert multihost.detect_dead_hosts(
        root, timeout_s=30.0, expected_hosts=range(4), now=110.0) == [2, 3]


def test_reform_plan_ranks_and_resharded_reader(tmp_path):
    root = str(tmp_path)
    for h, ts in ((0, 100.0), (1, 40.0), (2, 100.0), (3, 100.0)):
        multihost.write_heartbeat(root, host_id=h, now=ts)
    plan = multihost.plan_reform(root, timeout_s=30.0, now=110.0)
    assert plan.dead == [1]
    assert plan.survivors == [0, 2, 3]
    assert plan.rank_of == {0: 0, 2: 1, 3: 2}     # contiguous re-rank
    # disjoint coverage over the SURVIVING count
    items = list(range(9))
    shards = [list(plan.sharded_reader(lambda: iter(items), host_id=h)())
              for h in plan.survivors]
    assert sorted(x for s in shards for x in s) == items
    with pytest.raises(ValueError, match="not a survivor"):
        plan.sharded_reader(lambda: iter(items), host_id=1)


def test_reform_builds_mesh_over_survivors(tmp_path):
    root = str(tmp_path)
    multihost.write_heartbeat(root, host_id=0)      # fresh (real clock)
    mesh, plan = multihost.reform(root, timeout_s=30.0,
                                  expected_hosts=[0, 1])
    assert plan.dead == [1] and plan.host_count == 1
    # single-process test topology: the mesh spans the live local devices
    assert mesh.devices.size == jax.device_count()


def test_heartbeat_thread_beats_and_stops(tmp_path):
    hb = multihost.HostHeartbeat(str(tmp_path), interval_s=0.01, host_id=7)
    with hb:
        deadline = time.time() + 5
        path = multihost.heartbeat_path(str(tmp_path), 7)
        while time.time() < deadline:
            beats = multihost.read_heartbeats(str(tmp_path))
            if beats.get(7, {}).get("seq", 0) >= 2:
                break
            time.sleep(0.01)
    assert os.path.exists(path)
    assert multihost.read_heartbeats(str(tmp_path))[7]["seq"] >= 2
    assert hb._thread is None             # joined


def test_supervisor_keeps_heartbeat_fresh(tmp_path):
    ck = str(tmp_path / "ck")
    res = run_resilient(
        lambda: make_trainer(steps_per_call=2), reader,
        checkpoint_dir=ck, num_passes=1, log_period=0, backoff_s=0.001,
        heartbeat_interval_s=0.05)
    assert res.status == "completed"
    beats = multihost.read_heartbeats(ck)
    assert beats and beats[0]["seq"] >= 1


# ---------------------------------------------------------------------------
# restart/fallback telemetry records
# ---------------------------------------------------------------------------

def test_restart_emits_telemetry_record(tmp_path):
    from paddle_tpu.obs import InMemorySink, Telemetry
    mem = InMemorySink()
    tel = Telemetry(sinks=[mem], health=False, memory=False)
    fs = FaultSchedule(crash_at_step=5)
    res = run_resilient(
        lambda: make_trainer(faults=fs, steps_per_call=2, telemetry=tel),
        reader, checkpoint_dir=str(tmp_path / "ck"), num_passes=1,
        saving_period=4, log_period=0, backoff_s=0.001)
    assert res.status == "completed"
    restarts = mem.by_kind("restart")
    assert len(restarts) == 1
    assert restarts[0]["failure"] == "crash" and restarts[0]["step"] == 5
    assert restarts[0]["backoff_s"] >= 0
