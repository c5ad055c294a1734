"""Distributed supervision tests (docs/resilience.md §Supervision).

Fast tier: heartbeat channels (file + TCP, EOF and stale-beat
detection, clean goodbyes), the hung-collective watchdog firing on an
injected ``collective.stall`` with site attribution, the exit-44 rescue
protocol (verified ``local_npz`` emergency tags, bit-exact bf16
round-trip, failed-save → exit 1), the resumable-dataloader cursor
(8-step == 4+resume parity, prefetch lookahead excluded), multi-process
fault plans (``DS_FAULT_PLAN``), the dist-init retry deadline fix, the
elastic world-shrink math, launcher peer-grace/exit-aggregation and the
runner's ``--restarts`` loop.

Slow tier (``supervision`` marker, CI job ``supervision``): the
2-real-process proof — ``kill -9`` one rank mid-step through the full
``runner --restarts 1 → launch → engine`` chain; the survivor detects
the death via heartbeat EOF (not timeout-only), commits a verified
emergency tag, exits 44, the launcher relaunches at the shrunk world,
and training resumes from that tag with the loader cursor intact (no
replayed batches) — plus the resharding-compatible ZeRO-Infinity
masters restore across topologies.
"""
import json
import os
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

from deepspeed_tpu.resilience import FaultInjector, manager
from deepspeed_tpu.resilience import faults
from deepspeed_tpu.resilience.supervision import (
    EXIT_PEER_FAILED_SAVED,
    FileBeatChannel,
    PeerFailure,
    Supervisor,
    TcpBeatChannel,
    emergency_local_save,
    load_local_state,
    supervised_sync,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "distributed_worker.py")


def _wait_for(predicate, timeout=8.0, period=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(period)
    return predicate()


# ---------------------------------------------------------------------------
# heartbeat channels
# ---------------------------------------------------------------------------


class TestFileBeatChannel:
    def test_stale_beat_declares_death(self, tmp_path):
        mon = FileBeatChannel(str(tmp_path), rank=0, world_size=2, beat_timeout=0.3)
        peer = FileBeatChannel(str(tmp_path), rank=1, world_size=2, beat_timeout=0.3)
        peer.beat(1)
        assert mon.events() == []  # fresh beat: alive
        time.sleep(0.6)  # beat goes stale
        events = mon.events()
        assert [e.kind for e in events] == ["dead"]
        assert events[0].rank == 1 and "stale" in events[0].reason

    def test_goodbye_is_not_death(self, tmp_path):
        mon = FileBeatChannel(str(tmp_path), rank=0, world_size=2, beat_timeout=0.3)
        peer = FileBeatChannel(str(tmp_path), rank=1, world_size=2, beat_timeout=0.3)
        peer.beat(1)
        peer.goodbye()
        time.sleep(0.5)
        events = mon.events()
        assert [e.kind for e in events] == ["bye"]
        assert mon.events() == []  # deduped


class TestTcpBeatChannel:
    def _pair(self, beat_timeout=5.0):
        srv = TcpBeatChannel(rank=0, world_size=2, port=0, beat_timeout=beat_timeout,
                             connect_grace=5.0)
        srv.start()
        cli = TcpBeatChannel(rank=1, world_size=2, address="127.0.0.1", port=srv.port,
                             beat_timeout=beat_timeout, connect_grace=5.0)
        cli.start()
        return srv, cli

    def test_eof_detection_names_the_dead_rank(self):
        srv, cli = self._pair()
        try:
            assert _wait_for(lambda: cli._client is not None)
            cli.beat(1)
            assert _wait_for(lambda: 1 in srv._last_beat)
            # abrupt close, no goodbye: the SIGKILL signature
            cli._stop.set()
            cli._client.close()
            assert _wait_for(lambda: any(e.rank == 1 and e.kind == "dead"
                                         for e in srv.events()))
        finally:
            srv.stop()
            cli.stop()

    def test_client_detects_server_death_and_bye_is_clean(self):
        srv, cli = self._pair()
        try:
            assert _wait_for(lambda: cli._client is not None)
            cli.goodbye()  # clean departure first: server records bye
            assert _wait_for(lambda: any(e.rank == 1 and e.kind == "bye"
                                         for e in srv.events()))
        finally:
            srv.stop()
            cli.stop()
        # a fresh pair where the SERVER vanishes: client raises rank-0 death
        srv2, cli2 = self._pair()
        try:
            assert _wait_for(lambda: cli2._client is not None)
            srv2.stop()  # server process "dies": all its sockets close
            assert _wait_for(lambda: any(e.rank == 0 and e.kind == "dead"
                                         for e in cli2.events()))
        finally:
            cli2.stop()

    def test_stale_beat_timeout_on_connected_client(self):
        srv, cli = self._pair(beat_timeout=0.4)
        try:
            assert _wait_for(lambda: cli._client is not None)
            cli.beat(1)
            assert _wait_for(lambda: 1 in srv._last_beat)
            time.sleep(0.8)  # connected but silent: the wedged-rank case
            assert any(e.rank == 1 and e.kind == "dead" and "stale" in e.reason
                       for e in srv.events())
        finally:
            srv.stop()
            cli.stop()


# ---------------------------------------------------------------------------
# supervisor: peer death, armed deadlines, stall attribution
# ---------------------------------------------------------------------------


def _supervisor(tmp_path, world_size=1, rank=0, on_rescue=None, **kw):
    channel = FileBeatChannel(str(tmp_path / "beats"), rank=rank, world_size=world_size,
                              beat_timeout=kw.pop("beat_timeout", 0.4))
    defaults = dict(beat_interval=0.05, sync_timeout=60.0, rescue_grace=5.0)
    defaults.update(kw)
    return Supervisor(rank=rank, world_size=world_size, channel=channel,
                      on_rescue=on_rescue, **defaults)


def test_supervisor_detects_peer_death_via_channel(tmp_path):
    rescues = []
    sup = _supervisor(tmp_path, world_size=2,
                      on_rescue=lambda site, reason: rescues.append((site, reason)))
    peer = FileBeatChannel(str(tmp_path / "beats"), rank=1, world_size=2, beat_timeout=0.4)
    peer.beat(1)
    sup.start()
    try:
        assert _wait_for(lambda: rescues, timeout=10)  # beat goes stale -> rescue
        assert sup.peer_failure is not None and sup.peer_failure.rank == 1
        assert "rank 1" in rescues[0][1]
    finally:
        sup.stop()


def test_hung_collective_watchdog_fires_and_attributes_stalled_site(tmp_path):
    """Acceptance: the watchdog fires on an injected ``collective.stall``
    and names the stuck site."""
    rescues = []
    sup = _supervisor(tmp_path, sync_timeout=0.3,
                      on_rescue=lambda site, reason: rescues.append((site, reason)))
    sup.start()
    inj = FaultInjector(seed=0).stall("collective.stall", seconds=1.2)
    try:
        with inj:
            t0 = time.monotonic()
            supervised_sync("step_boundary", supervisor=sup)
            waited = time.monotonic() - t0
        assert waited >= 1.0  # the stall really blocked the "collective"
        assert _wait_for(lambda: rescues, timeout=5)
        site, reason = rescues[0]
        assert site == "barrier:step_boundary"  # attribution
        assert "deadline" in reason or "hung" in reason
        assert sup.last_stuck_site == "barrier:step_boundary"
    finally:
        sup.stop()


def test_armed_region_disarms_on_normal_exit(tmp_path):
    rescues = []
    sup = _supervisor(tmp_path, sync_timeout=0.3, on_rescue=lambda *a: rescues.append(a))
    sup.start()
    try:
        with sup.armed("quick"):
            time.sleep(0.05)
        time.sleep(0.6)  # past the deadline — but the region closed in time
        assert rescues == []
    finally:
        sup.stop()


def test_hb_drop_fault_site_suppresses_beats(tmp_path):
    sup = _supervisor(tmp_path, world_size=2, beat_interval=0.03,
                      on_rescue=lambda *a: None)
    inj = FaultInjector(seed=0).flag("hb.drop", times=10_000)
    beat_file = tmp_path / "beats" / "rank0.beat"
    with inj:
        sup.start()
        time.sleep(0.4)
        sup.stop()
    # every beat was dropped: only the goodbye from stop() landed
    data = json.loads(beat_file.read_text())
    assert data.get("bye") is True and "seq" not in data


# ---------------------------------------------------------------------------
# rescue: emergency local_npz tags
# ---------------------------------------------------------------------------


def _snapshot_tree():
    import jax.numpy as jnp

    return {
        "params": {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
                   "h": np.ones((2, 2), jnp.bfloat16)},
        "global_step": np.int32(7),
    }


def test_rescue_save_commits_verified_tag_and_exits_44(tmp_path):
    sup = _supervisor(tmp_path, save_dir_fn=lambda: str(tmp_path / "ckpt"))
    snap = _snapshot_tree()
    sup.snapshot.update(snap, {"global_step": 7, "client_state": {}})
    code = sup.rescue_save(reason="unit-test peer death")
    assert code == EXIT_PEER_FAILED_SAVED == 44
    root = str(tmp_path / "ckpt")
    tags = manager.newest_first(root)
    assert tags == ["emergency_step7_rank0"]
    ok, notes = manager.verify_tag(root, tags[0])
    assert ok, notes
    meta = json.load(open(os.path.join(root, tags[0], "meta.json")))
    assert meta["format"] == "local_npz" and meta["rescue_reason"] == "unit-test peer death"
    # bit-exact round-trip, including the bf16 leaf
    restored = load_local_state(os.path.join(root, tags[0]), snap)
    assert restored["params"]["h"].dtype == snap["params"]["h"].dtype
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]), snap["params"]["w"])
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["h"], np.float32),
        np.asarray(snap["params"]["h"], np.float32),
    )


def test_rescue_without_snapshot_or_dir_exits_1(tmp_path):
    sup = _supervisor(tmp_path)  # no save dir, no snapshot
    assert sup.rescue_save(reason="x") == 1
    sup2 = _supervisor(tmp_path, save_dir_fn=lambda: str(tmp_path / "ckpt"))
    assert sup2.rescue_save(reason="x") == 1  # dir but no snapshot


def test_emergency_save_failure_never_reports_saved(tmp_path):
    sup = _supervisor(tmp_path, save_dir_fn=lambda: str(tmp_path / "ckpt"))
    sup.snapshot.update(_snapshot_tree(), {"global_step": 7})
    inj = FaultInjector(seed=0)
    inj.fail("ckpt.commit", times=1)
    with inj:
        assert sup.rescue_save(reason="x") == 1  # failed commit -> crash contract
    # the atomic protocol left no committed tag behind
    assert manager.committed_tags(str(tmp_path / "ckpt")) == []
    # and a later healthy attempt still succeeds (stage ownership released)
    assert sup.rescue_save(reason="x") == 44


def test_local_npz_missing_leaf_restores_zeros(tmp_path):
    snap = {"a": np.ones(3, np.float32)}
    path = emergency_local_save(str(tmp_path), "t", snap, {"global_step": 1})
    target = {"a": np.zeros(3, np.float32), "b": np.full((2,), 9.0, np.float32)}
    out = load_local_state(path, target)
    np.testing.assert_array_equal(out["a"], snap["a"])
    np.testing.assert_array_equal(out["b"], np.zeros(2, np.float32))


# ---------------------------------------------------------------------------
# engine integration: peer failure at a step boundary -> tag + exit 44,
# and the local_npz tag restores into a fresh engine
# ---------------------------------------------------------------------------


def _supervised_engine(tmp_path, register_loader=False):
    import deepspeed_tpu
    from tests.simple_model import base_config, simple_model_init, simple_model_loss

    ckpt = str(tmp_path / "ckpt")
    cfg = base_config(stage=0, micro_bs=1)
    cfg["resilience"] = {
        "watchdog": {"enabled": False, "save_dir": ckpt},
        "supervision": {"enabled": True, "channel": "file",
                        "beat_dir": str(tmp_path / "beats"),
                        "beat_interval_seconds": 0.05,
                        "beat_timeout_seconds": 0.5,
                        "rescue_grace_seconds": 5.0},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=simple_model_loss, model_parameters=simple_model_init(16), config=cfg
    )
    return engine, ckpt


def test_engine_peer_failure_saves_emergency_tag_and_exits_44(tmp_path):
    from tests.simple_model import random_batches

    engine, ckpt = _supervised_engine(tmp_path)
    assert engine._supervision is not None
    batches = random_batches(4, 8, 16, seed=3)
    for b in batches[:2]:
        engine.train_batch(b)
    # a peer dies; the next step boundary must rescue
    engine._supervision.peer_failure = PeerFailure(rank=1, reason="injected unit-test death")
    with pytest.raises(SystemExit) as exc:
        engine.train_batch(batches[2])
    assert exc.value.code == 44
    tags = manager.newest_first(ckpt)
    assert tags and tags[0].startswith("emergency_step3")
    ok, notes = manager.verify_tag(ckpt, tags[0])
    assert ok, notes

    # a FRESH engine (supervision off) resumes from the emergency tag
    # and keeps training — the local_npz restore path end-to-end
    import deepspeed_tpu
    from tests.simple_model import base_config, simple_model_init, simple_model_loss

    engine2, _, _, _ = deepspeed_tpu.initialize(
        model=simple_model_loss, model_parameters=simple_model_init(16, seed=9),
        config=base_config(stage=0, micro_bs=1),
    )
    path, _ = engine2.load_checkpoint(ckpt)
    assert path is not None and engine2._host_global_step == 3
    loss = float(engine2.train_batch(batches[3]))
    assert np.isfinite(loss)


# ---------------------------------------------------------------------------
# resumable dataloaders
# ---------------------------------------------------------------------------


def _batch_key(b):
    return float(np.sum(b["x"])) if isinstance(b, dict) else float(np.sum(b))


def test_loader_resume_parity_8_vs_4_plus_resume(tmp_path):
    """Satellite acceptance: uninterrupted 8-step run == 4-step run +
    save/load resume — identical batch sequence AND losses."""
    import deepspeed_tpu
    from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader
    from tests.simple_model import base_config, random_dataset, simple_model_init, simple_model_loss

    data = random_dataset(12, 8, 16, seed=5)

    def make(seed=0):
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=simple_model_loss, model_parameters=simple_model_init(16), config=base_config(stage=0, micro_bs=1)
        )
        loader = DeepSpeedDataLoader(data, batch_size=8, shuffle=True, seed=11,
                                     process_index=0, process_count=1)
        engine.register_dataloader(loader)
        return engine, loader

    # reference: 8 uninterrupted steps
    eng_a, loader_a = make()
    ref = [( _batch_key(b), float(eng_a.train_batch(b)) )
           for _, b in zip(range(8), loader_a)]

    # interrupted: 4 steps, checkpoint (cursor rides in client_state)
    eng_b, loader_b = make()
    first = [(_batch_key(b), float(eng_b.train_batch(b)))
             for _, b in zip(range(4), loader_b)]
    eng_b.save_checkpoint(str(tmp_path / "ck"))

    # resume: fresh engine + fresh loader, cursor restored on load
    eng_c, loader_c = make()
    path, cs = eng_c.load_checkpoint(str(tmp_path / "ck"))
    assert path is not None and cs.get("__dataloader__", {}).get("cursor") == 4
    second = [(_batch_key(b), float(eng_c.train_batch(b)))
              for _, b in zip(range(4), loader_c)]

    resumed = first + second
    # identical batch sequence: no replays, no skips
    np.testing.assert_array_equal([k for k, _ in resumed], [k for k, _ in ref])
    np.testing.assert_allclose([l for _, l in resumed], [l for _, l in ref],
                               rtol=1e-5, atol=1e-6)


def test_prefetch_wrappers_exclude_inflight_lookahead(tmp_path):
    """The wrapped loaders pull ahead of training; their state_dict must
    report the CONSUMED cursor, not the prefetched one."""
    from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader, DevicePrefetchLoader
    from tests.simple_model import random_dataset

    data = random_dataset(10, 4, 8, seed=1)

    def consumed(loader_cls_kw):
        inner = DeepSpeedDataLoader(data, batch_size=4, shuffle=True, seed=2,
                                    process_index=0, process_count=1)
        wrapped = DevicePrefetchLoader(inner, prefetch_depth=4, **loader_cls_kw)
        it = iter(wrapped)
        got = [next(it) for _ in range(3)]
        time.sleep(0.2)  # let the prefetcher run ahead
        return wrapped, got

    wrapped, got = consumed({})
    sd = wrapped.state_dict()
    assert sd["cursor"] == 3  # inner loader is ahead; the wrapper is honest
    assert wrapped.loader._cursor > 3 or wrapped.loader._cursor == 10

    # resuming from that cursor yields exactly the 4th batch next
    inner2 = DeepSpeedDataLoader(data, batch_size=4, shuffle=True, seed=2,
                                 process_index=0, process_count=1)
    inner2.load_state_dict(sd)
    nxt = next(iter(inner2))
    ref_inner = DeepSpeedDataLoader(data, batch_size=4, shuffle=True, seed=2,
                                    process_index=0, process_count=1)
    ref = [b for _, b in zip(range(4), ref_inner)]
    np.testing.assert_array_equal(nxt["x"], ref[3]["x"])


def test_overlap_prefetcher_state_dict_delegation():
    from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader
    from deepspeed_tpu.runtime.overlap import DevicePrefetcher, InlineLoader
    from tests.simple_model import random_dataset

    data = random_dataset(8, 4, 8, seed=1)
    inner = DeepSpeedDataLoader(data, batch_size=4, shuffle=False,
                                process_index=0, process_count=1)
    pf = DevicePrefetcher(inner, depth=3, place_fn=lambda b: b)
    it = iter(pf)
    next(it), next(it)
    time.sleep(0.2)
    assert pf.state_dict()["cursor"] == 2
    pf.close()

    inline = InlineLoader(
        DeepSpeedDataLoader(data, batch_size=4, shuffle=False,
                            process_index=0, process_count=1),
        place_fn=lambda b: b,
    )
    it = iter(inline)
    next(it)
    assert inline.state_dict()["cursor"] == 1


# ---------------------------------------------------------------------------
# fault plans across processes
# ---------------------------------------------------------------------------


def test_fault_plan_rank_filter_and_env_install(monkeypatch):
    plan = faults.plan_json([
        {"site": "step.boundary", "action": "sigkill", "rank": 1, "after": 3},
        {"site": "collective.stall", "action": "stall", "seconds": 0.5},
        {"site": "hb.drop", "action": "flag", "rank": [0, 2], "times": 5},
    ])
    inj0 = FaultInjector.from_plan(plan, rank=0)
    assert sorted(inj0._plans) == ["collective.stall", "hb.drop"]
    inj1 = FaultInjector.from_plan(plan, rank=1)
    assert sorted(inj1._plans) == ["collective.stall", "step.boundary"]
    assert inj1._plans["step.boundary"]["kind"] == "sigkill"

    monkeypatch.setenv("DS_FAULT_PLAN", plan)
    monkeypatch.setenv("RANK", "2")
    installed = faults.install_from_env()
    try:
        assert installed is not None
        assert faults.check_flag("hb.drop") is True
    finally:
        faults._ACTIVE = None


def test_fault_plan_roundtrip_through_injector():
    inj = FaultInjector(seed=3)
    inj.fail("ckpt.commit", times=2).stall("collective.stall", 0.7).sigkill("step.boundary", after=1)
    back = FaultInjector.from_plan(inj.to_plan())
    assert back._plans["ckpt.commit"]["times"] == 2
    assert back._plans["collective.stall"]["seconds"] == 0.7
    assert back._plans["step.boundary"]["kind"] == "sigkill"


def test_check_stall_sleeps_and_logs():
    inj = FaultInjector(seed=0).stall("collective.stall", 0.2)
    with inj:
        t0 = time.monotonic()
        slept = faults.check_stall("collective.stall")
        assert slept == 0.2 and time.monotonic() - t0 >= 0.18
        assert faults.check_stall("collective.stall") == 0.0  # times=1 spent
    assert ("collective.stall", "stall") in inj.log


# ---------------------------------------------------------------------------
# dist-init retry deadline (satellite bugfix)
# ---------------------------------------------------------------------------


def test_dist_init_retry_honors_deadline_and_names_coordinator(monkeypatch):
    from deepspeed_tpu.comm import distributed as dist
    from deepspeed_tpu.resilience.policy import RetryError

    calls = {}

    def fake_initialize(coordinator_address=None, num_processes=None, process_id=None,
                        initialization_timeout=None):
        calls.setdefault("kw", []).append(initialization_timeout)
        raise RuntimeError("connection refused (simulated)")

    monkeypatch.setattr(jax.distributed, "initialize", fake_initialize)
    monkeypatch.setattr(dist, "_initialized", False)
    monkeypatch.setenv("DS_DIST_INIT_RETRIES", "2")
    monkeypatch.setenv("DS_DIST_INIT_BACKOFF", "0.01")
    monkeypatch.setenv("DS_DIST_INIT_DEADLINE", "7")
    with pytest.raises(RetryError) as exc:
        dist.init_distributed(
            coordinator_address="badhost:1", num_processes=2, process_id=0, verbose=False
        )
    msg = str(exc.value)
    # the error names the coordinator, the attempt count and the deadline
    assert "badhost:1" in msg and "2 attempt(s)" in msg and "7" in msg
    # the per-call initialize timeout was bounded by the deadline too
    assert calls["kw"] and all(t == 7 for t in calls["kw"])
    assert not dist.is_initialized()


# ---------------------------------------------------------------------------
# elastic world shrink math
# ---------------------------------------------------------------------------


def test_shrink_world_info_drops_failed_slots_and_empty_hosts():
    from deepspeed_tpu.elasticity.elasticity import shrink_world_info, world_rank_map

    active = {"h0": [0, 1], "h1": [0, 1], "h2": [0]}
    assert world_rank_map(active) == [("h0", 0), ("h0", 1), ("h1", 0), ("h1", 1), ("h2", 0)]
    out = shrink_world_info(active, [1, 4])
    assert out == {"h0": [0], "h1": [0, 1]}
    out = shrink_world_info(active, [2, 3])  # whole h1 dies
    assert out == {"h0": [0, 1], "h2": [0]}
    with pytest.raises(ValueError):
        shrink_world_info(active, [9])


# ---------------------------------------------------------------------------
# launcher chain: peer grace, exit aggregation, --restarts
# ---------------------------------------------------------------------------

_CLEAN_ENV = {"PATH": "/usr/bin:/bin", "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"}


def test_launch_peer_grace_prefers_survivor_exit_44(tmp_path):
    """A SIGKILL'd rank opens the grace window; the survivor's exit 44
    wins the aggregation, and the per-rank codes land in the status
    file for the runner's shrink."""
    from deepspeed_tpu.launcher.runner import encode_world_info

    script = tmp_path / "child.py"
    script.write_text(
        "import os, signal, sys, time\n"
        "if os.environ['RANK'] == '1':\n"
        "    os.kill(os.getpid(), signal.SIGKILL)\n"
        "time.sleep(1.0)\n"  # outlive the sibling's death, then 'save'
        "sys.exit(44)\n"
    )
    status_dir = tmp_path / "status"
    enc = encode_world_info({"localhost": [0, 1]})
    res = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher.launch",
         "--node_rank=0", "--world_info", enc, "--procs_per_node", "2",
         "--peer_grace", "20", str(script)],
        capture_output=True, text=True, timeout=90,
        env={**_CLEAN_ENV, "DS_SUPERVISION_DIR": str(status_dir)},
    )
    assert res.returncode == 44, res.stderr[-2000:]
    status = json.load(open(status_dir / "node0_status.json"))
    assert status["codes"]["1"] == 128 + signal.SIGKILL
    assert status["codes"]["0"] == 44
    assert status["exit_code"] == 44


def test_launch_plain_nonzero_exit_still_kills_pack_immediately(tmp_path):
    from deepspeed_tpu.launcher.runner import encode_world_info

    script = tmp_path / "child.py"
    script.write_text(
        "import os, sys, time\n"
        "if os.environ['RANK'] == '1':\n"
        "    sys.exit(3)\n"
        "time.sleep(30)\n"
    )
    enc = encode_world_info({"localhost": [0, 1]})
    t0 = time.monotonic()
    res = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher.launch",
         "--node_rank=0", "--world_info", enc, "--procs_per_node", "2", str(script)],
        capture_output=True, text=True, timeout=60, env=_CLEAN_ENV,
    )
    assert res.returncode == 3
    assert time.monotonic() - t0 < 25  # no grace window for a plain exit


def test_launch_exports_supervision_endpoint(tmp_path):
    from deepspeed_tpu.launcher.runner import encode_world_info

    script = tmp_path / "child.py"
    script.write_text(
        "import os\n"
        f"open(os.path.join({str(tmp_path)!r}, 'env' + os.environ['RANK']), 'w').write(\n"
        "    os.environ['DS_SUPERVISION_ADDR'] + ':' + os.environ['DS_SUPERVISION_PORT'])\n"
    )
    enc = encode_world_info({"localhost": [0, 1]})
    res = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher.launch",
         "--node_rank=0", "--master_port", "29123", "--world_info", enc,
         "--procs_per_node", "2", str(script)],
        capture_output=True, text=True, timeout=60, env=_CLEAN_ENV,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    assert (tmp_path / "env0").read_text() == "127.0.0.1:29140"  # master_port + 17
    assert (tmp_path / "env0").read_text() == (tmp_path / "env1").read_text()


def test_runner_restarts_relaunches_at_shrunk_world(tmp_path):
    """The elastic restart driver end-to-end (no jax): life 0 loses rank
    1 to SIGKILL and rank 0 exits 44; the runner must relaunch ONCE at
    world size 1 and propagate the clean exit."""
    script = tmp_path / "train.py"
    script.write_text(
        "import json, os, signal, sys, time\n"
        f"out = {str(tmp_path)!r}\n"
        "life = os.environ.get('DS_RESTART_COUNT', '0')\n"
        "ws = os.environ['WORLD_SIZE']\n"
        "open(os.path.join(out, f'life{life}_rank' + os.environ['RANK']), 'w').write(ws)\n"
        "if life == '0':\n"
        "    if os.environ['RANK'] == '1':\n"
        "        os.kill(os.getpid(), signal.SIGKILL)\n"
        "    time.sleep(1.0)\n"
        "    sys.exit(44)\n"
        "sys.exit(0)\n"
    )
    res = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher.runner",
         "--num_gpus", "2", "--restarts", "1", str(script)],
        capture_output=True, text=True, timeout=120,
        env={**_CLEAN_ENV, "DS_PEER_GRACE": "20"},
    )
    assert res.returncode == 0, f"rc={res.returncode}\n{res.stderr[-3000:]}"
    assert (tmp_path / "life0_rank0").read_text() == "2"
    assert (tmp_path / "life1_rank0").read_text() == "1"  # shrunk world
    assert not (tmp_path / "life1_rank1").exists()  # dead slot dropped


def test_runner_restart_budget_exhausts(tmp_path):
    script = tmp_path / "train.py"
    script.write_text("import sys\nsys.exit(43)\n")
    res = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher.runner",
         "--num_gpus", "1", "--restarts", "1", str(script)],
        capture_output=True, text=True, timeout=60, env=_CLEAN_ENV,
    )
    assert res.returncode == 43
    assert "restart budget" in (res.stdout + res.stderr)


# ---------------------------------------------------------------------------
# the 2-real-process proofs (slow; CI `supervision` job)
# ---------------------------------------------------------------------------


def _run_supervised(out_dir, nprocs, steps=8, restarts=0, extra_env=None, timeout=900):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env.update(extra_env or {})
    with socket.socket() as s:
        s.bind(("", 0))
        port = s.getsockname()[1]
    args = ["--out", str(out_dir), "--mode", "supervised",
            "--local_devices", "2", "--steps", str(steps)]
    if nprocs == 1 and not restarts:
        cmd = [sys.executable, WORKER, *args]
        env.setdefault("WORLD_SIZE", "1")
    else:
        cmd = [sys.executable, "-m", "deepspeed_tpu.launcher.runner",
               "--num_gpus", str(nprocs), "--master_port", str(port),
               "--restarts", str(restarts), WORKER, *args]
    return subprocess.run(cmd, env=env, cwd=REPO, capture_output=True, text=True,
                          timeout=timeout)


def _records(out_dir, life, rank=0):
    with open(os.path.join(str(out_dir), f"life{life}_rank{rank}.jsonl")) as f:
        return json.load(f)


@pytest.mark.slow
@pytest.mark.supervision
def test_two_process_kill_one_rank_elastic_restart(tmp_path):
    """THE acceptance scenario: ``kill -9`` one rank mid-step; within a
    single ``--restarts 1`` invocation the survivor detects the death
    via the heartbeat channel (socket EOF, not timeout-only), commits a
    verified emergency tag, exits 44, the launcher relaunches at the
    shrunk world, and training resumes from that tag with the loader
    cursor intact — batch sequence and losses match an uninterrupted
    single-process run."""
    out = tmp_path / "multi"
    plan = faults.plan_json([
        {"site": "step.boundary", "action": "sigkill", "rank": 1, "after": 3}
    ])
    res = _run_supervised(out, nprocs=2, steps=8, restarts=1,
                          extra_env={"DS_FAULT_PLAN": plan, "DS_PEER_GRACE": "60"})
    assert res.returncode == 0, (
        f"rc={res.returncode}\nstdout:{res.stdout[-2000:]}\nstderr:{res.stderr[-4000:]}"
    )

    # the emergency tag: committed, verified, attributed to the heartbeat
    # channel (socket EOF — detection, not timeout inference)
    ckpt = str(out / "ckpt")
    tags = manager.newest_first(ckpt)
    emergency = [t for t in tags if t.startswith("emergency_")]
    assert emergency, tags
    ok, notes = manager.verify_tag(ckpt, emergency[0])
    assert ok, notes
    meta = json.load(open(os.path.join(ckpt, emergency[0], "meta.json")))
    assert meta["format"] == "local_npz"
    assert "rank 1" in meta["rescue_reason"]
    assert "EOF" in meta["rescue_reason"] or "died" in meta["rescue_reason"], meta["rescue_reason"]

    # telemetry cross-rank aggregation (docs/telemetry.md): rank-local
    # metrics piggybacked on the beat channel reached rank 0 BEFORE the
    # kill (an aggregate line covers both ranks), and the killed rank
    # shows up as dead — with its last-seen snapshot — in the same
    # exported stream the metrics ride in.
    agg_path = out / "telemetry" / "aggregate_rank0.jsonl"
    assert agg_path.exists(), "rank-0 aggregate stream missing"
    agg_lines = [json.loads(l) for l in agg_path.read_text().splitlines() if l.strip()]
    assert any(
        len(l["alive"]) == 2 and any(row["n"] == 2 for row in l["metrics"].values())
        for l in agg_lines
    ), "no aggregate line ever covered both live ranks"
    dead_lines = [l for l in agg_lines if any(d["rank"] == 1 for d in l["dead"])]
    assert dead_lines, "killed rank never flagged dead in the aggregate stream"
    dead_row = next(d for d in dead_lines[-1]["dead"] if d["rank"] == 1)
    assert dead_row["last_metrics"], "dead rank's last-seen snapshot missing"

    # rank 1 died at ITS 4th boundary; rank 0 rescued at the boundary of
    # some step k shortly after.  Step k trained but its record was cut
    # off by the rescue — the tag certifies state AND loader cursor at k.
    k = meta["global_step"]
    assert 3 <= k <= 7, k  # detection landed mid-run (restart really resumed work)
    assert meta["client_state"]["__dataloader__"]["cursor"] == k
    life0 = _records(out, 0)
    assert [r["step"] for r in life0] == list(range(1, k)), (k, life0)
    # life 1 (shrunk world): resumed at exactly step k+1, finished at 8
    final1 = json.load(open(out / "final_life1_rank0.json"))
    assert final1["world"] == 1 and final1["steps"] == 8
    life1 = final1["records"]
    assert [r["step"] for r in life1] == list(range(k + 1, 9)), (k, life1)

    # parity with an uninterrupted single-process run: every recorded
    # step saw the SAME batch (no replays, no skips — the resumed loader
    # continued at cursor k) and the same loss
    ref_out = tmp_path / "single"
    ref = _run_supervised(ref_out, nprocs=1, steps=8)
    assert ref.returncode == 0, ref.stderr[-3000:]
    ref_recs = json.load(open(ref_out / "final_life0_rank0.json"))["records"]
    assert [r["step"] for r in ref_recs] == list(range(1, 9))
    ref_by_step = {r["step"]: r for r in ref_recs}
    for r in life0 + life1:
        assert r["batch"] == ref_by_step[r["step"]]["batch"], (k, r)
        np.testing.assert_allclose(r["loss"], ref_by_step[r["step"]]["loss"],
                                   rtol=5e-3, atol=5e-3)


@pytest.mark.slow
@pytest.mark.supervision
def test_zero_infinity_masters_reshard_compatible_restore(tmp_path):
    """The sharded-masters topology check relaxed to resharding-
    compatible: a checkpoint saved 'sharded over S ranks' restores into
    a differently-partitioned engine by reassembling ALL per-rank files
    and re-slicing, instead of demanding an identical topology."""
    import dataclasses
    import shutil

    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2

    mcfg = dataclasses.replace(
        gpt2.GPT2_TINY, n_layer=2, vocab_size=64, n_positions=32,
        remat=False, use_flash_attention=False,
    )
    model_fn, init_fn, tp_fn = gpt2.make_model(mcfg)
    cfg = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 3, "offload_param": {"device": "cpu", "buffer_count": 2}},
        "mesh": {"data": 4, "fsdp": 2},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "steps_per_print": 10_000,
    }

    def build():
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model_fn, model_parameters=init_fn(seed=0), config=cfg, tp_spec_fn=tp_fn
        )
        return engine

    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 64, (8, 24), dtype=np.int32)}
    eng = build()
    eng.train_batch(batch)  # moments become non-trivial
    src = tmp_path / "src"
    eng.save_checkpoint(str(src), tag="t")

    # forge a 'sharded over 2 ranks' checkpoint by splitting every
    # fsdp-sharded leaf of the real save along its sharded dim
    with np.load(src / "t" / "host_optimizer_rank0.npz") as z:
        full = {k.replace("::", "/"): z[k] for k in z.files}
    kinds = dict(zip(eng._host_opt.keys, eng._flat_leaf_kinds))
    halves = [{}, {}]
    for k in eng._host_opt.keys:
        kind, d = kinds[k]
        for pfx in ("master", "m", "v"):
            key = f"{pfx}/{k}"
            arr = full[key]
            if kind == "block" and d is not None:
                n = arr.shape[d] // 2
                sl0 = [slice(None)] * arr.ndim
                sl1 = [slice(None)] * arr.ndim
                sl0[d], sl1[d] = slice(0, n), slice(n, arr.shape[d])
                halves[0][key] = arr[tuple(sl0)]
                halves[1][key] = arr[tuple(sl1)]
            else:
                halves[0][key] = arr
                halves[1][key] = arr
    forged = tmp_path / "forged"
    os.makedirs(forged / "t")
    for r, h in enumerate(halves):
        np.savez(forged / "t" / f"host_optimizer_rank{r}.npz",
                 **{k.replace("/", "::"): v for k, v in h.items()})
    meta = json.load(open(src / "t" / "meta.json"))
    meta["masters_sharded"] = True
    meta["process_count"] = 2
    json.dump(meta, open(forged / "t" / "meta.json", "w"))
    (forged / "latest").write_text("t")

    eng2 = build()
    path, _ = eng2.load_checkpoint(str(forged))
    assert path is not None
    for a, b in zip(eng2._host_opt.masters, eng._host_opt.masters):
        np.testing.assert_array_equal(a, b)

    # with a rank file missing the relaxation cannot apply: strict error
    os.remove(forged / "t" / "host_optimizer_rank1.npz")
    eng3 = build()
    with pytest.raises(ValueError, match="resharded|matching topology"):
        eng3.load_checkpoint(str(forged))
