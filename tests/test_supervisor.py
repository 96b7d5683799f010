"""Real-fleet runtime: process supervisor, restart policy, striped restore.

Two layers of coverage:

* FAST units drive the Supervisor with trivial stand-in worker scripts
  (the ``cmd_builder`` seam exists exactly for this): restart-on-43,
  eviction + elastic gang re-mesh, failure-budget shutdown, hang
  detection, supervisor-side sigkill chaos, and the stripe-exchange
  transports.
* E2E drills launch REAL ``repro.launch.train`` worker processes under
  ``repro.launch.supervisor``: chaos kill -> exit 43 -> restart ->
  resume, with final params bit-identical to an uninterrupted fleet
  (compared via per-rank ``params_crc`` result files); a striped gang
  restore that reads strictly fewer checkpoint bytes per host than a
  full read (asserted from the obs-registry counters each worker
  exports); and an optional jax.distributed bring-up smoke.
"""
import json
import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

from repro.checkpoint import (CheckpointCorruptError, restore_checkpoint,
                              restore_checkpoint_striped, save_checkpoint)
from repro.obs import REGISTRY
from repro.runtime import (LocalStripeExchange, RestartPolicy,
                           StripeExchangeTimeout, Supervisor,
                           TcpStripeExchange, allocate_ports,
                           split_spec_strings)

ARCH = "qwen3-4b"
SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")

FAST = RestartPolicy(max_restarts_per_rank=2, max_total_failures=6,
                     backoff_base_s=0.05, backoff_max_s=0.2,
                     hang_timeout_s=1.0, term_grace_s=2.0)


# ---------------------------------------------------------------------------
# restart policy units
# ---------------------------------------------------------------------------

def test_backoff_deterministic_jittered_capped():
    p = RestartPolicy(backoff_base_s=0.25, backoff_max_s=2.0,
                      backoff_jitter=0.25)
    a = p.backoff_s(1, seed=0, rank=1)
    assert a == p.backoff_s(1, seed=0, rank=1)      # replayable
    assert a != p.backoff_s(1, seed=0, rank=2)      # decorrelated by rank
    assert 0.25 <= a <= 0.25 * 1.25                 # base + bounded jitter
    assert 0.5 <= p.backoff_s(2, seed=0, rank=1) <= 0.5 * 1.25
    assert p.backoff_s(10, seed=0, rank=1) <= 2.0 * 1.25   # capped


def test_split_spec_strings_partitions_supervisor_kinds():
    sup, wrk = split_spec_strings(
        ["kill@5", "sigkill@9:host=2", "diskfull@3"])
    assert sup == ["sigkill@9:host=2"]
    assert wrk == ["kill@5", "diskfull@3"]


# ---------------------------------------------------------------------------
# supervisor over stand-in workers (fast)
# ---------------------------------------------------------------------------

def _fake_builder(tmp_path, fleet_dir, body):
    """cmd_builder whose worker script runs `body` with rank/world/tag/
    attempt/fleet_dir bound and a heartbeat() helper in scope."""
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent("""\
        import json, os, sys, time
        rank, world, tag, attempt = map(int, sys.argv[1:5])
        fleet_dir = sys.argv[5]

        def heartbeat(step):
            d = os.path.join(fleet_dir, "hb")
            os.makedirs(d, exist_ok=True)
            p = os.path.join(d, f"rank_{tag}.json")
            with open(p + ".tmp", "w") as f:
                json.dump({"rank": rank, "step": step,
                           "wall": time.time()}, f)
            os.replace(p + ".tmp", p)
    """) + textwrap.dedent(body))

    def build(spec):
        return [sys.executable, str(script), str(spec.rank),
                str(spec.world), str(spec.tag), str(spec.attempt),
                fleet_dir]

    return build


def test_exit_43_restarts_until_success(tmp_path):
    fleet = str(tmp_path / "fleet")
    build = _fake_builder(tmp_path, fleet, """\
        heartbeat(attempt)
        sys.exit(43 if attempt == 1 else 0)
    """)
    report = Supervisor(2, build, fleet_dir=fleet, policy=FAST).run()
    assert report["outcome"] == "completed"
    assert report["total_failures"] == 2
    for w in report["workers"]:
        assert w["exit_history"] == [43, 0]
        assert w["attempts"] == 2 and w["state"] == "done"
    assert any(e["kind"] == "backoff" for e in report["events"])


def test_repeat_offender_evicted_and_gang_remeshed(tmp_path):
    """tag 1 fails every launch -> after the per-rank cap it is evicted;
    the surviving gang is SIGTERMed and relaunched re-meshed (world 2 ->
    1), after which it finishes: a degraded but completed fleet."""
    fleet = str(tmp_path / "fleet")
    build = _fake_builder(tmp_path, fleet, """\
        if tag == 1:
            sys.exit(1)
        if world == 1:
            sys.exit(0)       # post-remesh solo gang: finish
        time.sleep(60)        # pre-remesh: stay up until SIGTERMed
    """)
    policy = RestartPolicy(max_restarts_per_rank=1, max_total_failures=10,
                           backoff_base_s=0.05, backoff_max_s=0.1,
                           term_grace_s=2.0)
    report = Supervisor(2, build, fleet_dir=fleet, policy=policy).run()
    assert report["outcome"] == "degraded"
    by_tag = {w["tag"]: w for w in report["workers"]}
    assert by_tag[1]["state"] == "evicted"
    assert by_tag[0]["state"] == "done"
    assert report["plan"]["n_hosts"] == 1
    assert report["plan"]["data_parallel"] == 1
    assert report["plan"]["host_ranks"] in ({0: 0}, {"0": 0})
    kinds = [e["kind"] for e in report["events"]]
    assert "evict" in kinds and "remesh" in kinds


@pytest.mark.parametrize("platforms,nprocs,refused", [
    ("", 2, True), ("cpu", 2, False), ("", 1, False), ("tpu", 2, True),
    ("cpu,tpu", 2, True), (" CPU ", 2, False)])
def test_gang_refused_on_tpu_host(tmp_path, monkeypatch, platforms, nprocs,
                                  refused):
    """One process per chip: every JAX worker would claim all of a TPU
    host's chips, so the launchers refuse a gang of more than one there
    unless ``JAX_PLATFORMS`` keeps the workers off ``tpu``.  The generic
    Supervisor itself launches any process and refuses nothing."""
    from repro.launch import supervisor as launcher
    from repro.runtime import compat
    monkeypatch.setattr(compat, "tpu_chips_attached", lambda: 4)
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    fleet = str(tmp_path / "fleet")
    Supervisor(nprocs, _fake_builder(tmp_path, fleet, "pass\n"),
               fleet_dir=fleet)
    argv = ["--nprocs", str(nprocs), "--fleet-dir", fleet]
    if refused:
        with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
            launcher.main(argv)
        from types import SimpleNamespace

        from repro.launch.serve import run_fleet_supervised
        with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
            run_fleet_supervised(SimpleNamespace(fleet=nprocs))
    else:
        from repro.launch.mesh import refuse_gang_on_tpu
        refuse_gang_on_tpu(nprocs)


def test_failure_budget_exhaustion_shuts_down(tmp_path):
    fleet = str(tmp_path / "fleet")
    build = _fake_builder(tmp_path, fleet, "sys.exit(2)\n")
    policy = RestartPolicy(max_restarts_per_rank=10, max_total_failures=2,
                           backoff_base_s=0.05, backoff_max_s=0.1)
    report = Supervisor(2, build, fleet_dir=fleet, policy=policy).run()
    assert report["outcome"] == "budget_exhausted"
    assert report["total_failures"] == 3            # the one over budget
    assert any(e["kind"] == "escalate" for e in report["events"])
    assert all(w["state"] == "evicted" for w in report["workers"])


def test_hang_detector_kills_quiet_worker(tmp_path):
    """A worker that heartbeats once and goes dark (chaos partition /
    livelock) is SIGKILLed onto the ordinary restart path."""
    fleet = str(tmp_path / "fleet")
    build = _fake_builder(tmp_path, fleet, """\
        heartbeat(0)
        if attempt == 1:
            time.sleep(60)    # dark: no further heartbeats
        sys.exit(0)
    """)
    report = Supervisor(1, build, fleet_dir=fleet, policy=FAST).run()
    assert report["outcome"] == "completed"
    assert any(e["kind"] == "hang_kill" for e in report["events"])
    (w,) = report["workers"]
    assert w["exit_history"][0] == -9 and w["exit_history"][-1] == 0


def test_sigkill_chaos_fires_on_heartbeat_step(tmp_path):
    """Supervisor-side sigkill@N: an uncatchable SIGKILL once the target
    rank's heartbeat reaches step N — fired exactly once, so the restart
    (which replays the same steps) is not killed again."""
    fleet = str(tmp_path / "fleet")
    build = _fake_builder(tmp_path, fleet, """\
        heartbeat(100)
        if attempt == 1:
            time.sleep(60)
        sys.exit(0)
    """)
    report = Supervisor(1, build, fleet_dir=fleet, policy=FAST,
                        chaos_specs=["sigkill@50:host=0"]).run()
    assert report["outcome"] == "completed"
    assert [e["kind"] for e in report["events"]].count("chaos_sigkill") == 1
    (w,) = report["workers"]
    assert w["exit_history"] == [-9, 0]


# ---------------------------------------------------------------------------
# stripe exchange transports
# ---------------------------------------------------------------------------

def _threaded_allgather(exchanges, payloads, key="k"):
    world = len(payloads)
    out, errs = [None] * world, [None] * world

    def go(r):
        try:
            ex = exchanges[r] if isinstance(exchanges, list) else exchanges
            out[r] = ex.allgather(key, r, world, payloads[r])
        except Exception as e:           # surfaced to the test thread
            errs[r] = e

    ts = [threading.Thread(target=go, args=(r,)) for r in range(world)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return out, errs


def test_local_stripe_exchange_allgather_orders_by_rank():
    ex = LocalStripeExchange(3)
    payloads = [b"aaa", b"bb", b"c"]
    out, errs = _threaded_allgather(ex, payloads)
    assert errs == [None, None, None]
    assert all(got == payloads for got in out)


def test_local_stripe_exchange_timeout_is_timeout_error():
    """A missing peer is a TIMEOUT, never CheckpointCorruptError — the
    bytes on disk may be fine and falling back to an older checkpoint
    would silently lose steps."""
    assert issubclass(StripeExchangeTimeout, TimeoutError)
    assert not issubclass(StripeExchangeTimeout, CheckpointCorruptError)
    ex = LocalStripeExchange(2, timeout_s=0.2)
    with pytest.raises(StripeExchangeTimeout, match="ranks \\[1\\]"):
        ex.allgather("k", 0, 2, b"x")


def test_tcp_stripe_exchange_round_trip():
    ports = allocate_ports(2)
    exs = [TcpStripeExchange(r, ports, timeout_s=20) for r in range(2)]
    try:
        payloads = [b"\x00" * 70000, b"peer-bytes"]   # > one recv chunk
        out, errs = _threaded_allgather(exs, payloads)
        assert errs == [None, None]
        assert all(got == payloads for got in out)
    finally:
        for ex in exs:
            ex.close()


# ---------------------------------------------------------------------------
# striped restore: bit-identical, cheaper, corruption-detecting
# ---------------------------------------------------------------------------

def _striped_pair(path, step, like, world=2):
    ex = LocalStripeExchange(world)
    out, errs = [None] * world, [None] * world

    def go(r):
        try:
            out[r] = restore_checkpoint_striped(path, step, like, rank=r,
                                                world=world, exchange=ex)
        except Exception as e:
            errs[r] = e

    ts = [threading.Thread(target=go, args=(r,)) for r in range(world)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return out, errs


def test_striped_restore_matches_full_and_reads_fewer_bytes(tmp_path):
    path = str(tmp_path)
    rng = np.random.default_rng(0)
    tree = {"w": rng.normal(size=(64, 64)).astype(np.float32),
            "b": rng.normal(size=(64,)).astype(np.float32)}
    save_checkpoint(path, 9, tree)
    before = REGISTRY.snapshot()["counters"]
    out, errs = _striped_pair(path, 9, tree)
    assert errs == [None, None]
    full = restore_checkpoint(path, 9, tree)
    for got in out:
        np.testing.assert_array_equal(got["w"], full["w"])
        np.testing.assert_array_equal(got["b"], full["b"])
    after = REGISTRY.snapshot()["counters"]
    shard_bytes = os.path.getsize(
        os.path.join(path, "step_00000009", "shard_0.npz"))
    key = "checkpoint_read_bytes{mode=striped}"
    striped_delta = after.get(key, 0) - before.get(key, 0)
    # two ranks TOGETHER read ~one shard's worth; each strictly less
    assert 0 < striped_delta < 2 * shard_bytes
    assert striped_delta / 2 < shard_bytes


def test_striped_restore_detects_corruption_on_assembled_bytes(tmp_path):
    from repro.runtime.chaos import corrupt_checkpoint
    path = str(tmp_path)
    tree = {"w": np.arange(4096, dtype=np.float32)}
    save_checkpoint(path, 3, tree)
    corrupt_checkpoint(path, 3, mode="flip")
    out, errs = _striped_pair(path, 3, tree)
    assert out == [None, None]
    for e in errs:
        assert isinstance(e, CheckpointCorruptError)


# ---------------------------------------------------------------------------
# CLI exit-status contract (satellite: subprocess regression)
# ---------------------------------------------------------------------------

def _train_cli(*extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "repro.launch.train", "--arch", ARCH,
           "--smoke", "--steps", "8", "--seq-len", "32",
           "--global-batch", "4", *extra]
    return subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=300)


def test_chaos_kill_exits_43_from_cli(tmp_path):
    p = _train_cli("--chaos", "kill@4")
    assert p.returncode == 43, p.stderr


def test_chaos_kill_exit_43_survives_pending_save_error(tmp_path):
    """diskfull@4 leaves a failed async save pending when kill@6 fires;
    the preemption-grace wait must not let that OSError displace the
    kill — the supervisor keys its restart policy on status 43."""
    p = _train_cli("--ckpt-dir", str(tmp_path), "--ckpt-every", "2",
                   "--chaos", "diskfull@4", "--chaos", "kill@6")
    assert p.returncode == 43, p.stderr
    assert "disk full" in p.stdout      # the failure was logged, not fatal


# ---------------------------------------------------------------------------
# E2E drills: real train workers under the real supervisor
# ---------------------------------------------------------------------------

def _run_supervisor(args):
    from repro.launch.supervisor import main
    return main([str(a) for a in args])


def _fleet_args(ckpt_dir, fleet_dir, report, steps=8, **kw):
    args = ["--nprocs", 2, "--arch", ARCH, "--steps", steps,
            "--seq-len", 32, "--global-batch", 4,
            "--ckpt-dir", ckpt_dir, "--ckpt-every", 4,
            "--fleet-dir", fleet_dir, "--report-out", report]
    for k, v in kw.items():
        args += [f"--{k.replace('_', '-')}", v]
    return args


def _results(fleet_dir, tags=(0, 1)):
    out = {}
    for t in tags:
        with open(os.path.join(fleet_dir, f"result_rank{t}.json")) as f:
            out[t] = json.load(f)
    return out


@pytest.fixture(scope="module")
def baseline_fleet(tmp_path_factory):
    """One uninterrupted 2-worker fleet run: the reference params_crc and
    a committed checkpoint dir for the striped-restore drill."""
    root = tmp_path_factory.mktemp("fleet-baseline")
    ckpt, fleet = str(root / "ckpt"), str(root / "fleet")
    report = str(root / "report.json")
    assert _run_supervisor(_fleet_args(ckpt, fleet, report)) == 0
    with open(report) as f:
        rep = json.load(f)
    assert rep["outcome"] == "completed"
    assert rep["final_checkpoint_step"] == 8
    return {"ckpt": ckpt, "fleet": fleet, "results": _results(fleet)}


def test_fleet_kill_restart_resumes_bit_identical(baseline_fleet, tmp_path):
    """THE acceptance drill: chaos kill@5 on rank 1 -> worker exits 43 ->
    supervisor restarts it -> it resumes from the committed step-4
    checkpoint -> final params bit-identical to the uninterrupted fleet,
    on every rank."""
    ckpt, fleet = str(tmp_path / "ckpt"), str(tmp_path / "fleet")
    report = str(tmp_path / "report.json")
    assert _run_supervisor(_fleet_args(ckpt, fleet, report,
                                       chaos="kill@5")) == 0
    with open(report) as f:
        rep = json.load(f)
    assert rep["outcome"] == "completed"
    by_tag = {w["tag"]: w for w in rep["workers"]}
    assert by_tag[1]["exit_history"][0] == 43       # died AS exit status 43
    assert by_tag[1]["attempts"] == 2               # exactly one restart
    assert by_tag[0]["attempts"] == 1               # untargeted rank rode on
    ref = baseline_fleet["results"][0]["params_crc"]
    for t, res in _results(fleet).items():
        assert res["params_crc"] == ref, (t, res)


def test_fleet_striped_restore_reads_fewer_bytes_per_host(baseline_fleet,
                                                          tmp_path):
    """Gang restart over the baseline checkpoint with striped restore:
    every worker restores the SAME state while reading strictly fewer
    checkpoint-dir bytes than one full shard read, proven by the
    obs-registry counters each worker exports."""
    ckpt = baseline_fleet["ckpt"]
    shard = os.path.join(ckpt, "step_00000008", "shard_0.npz")
    full_bytes = os.path.getsize(shard)
    fleet = str(tmp_path / "fleet")
    report = str(tmp_path / "report.json")
    assert _run_supervisor(_fleet_args(ckpt, fleet, report, steps=12,
                                       striped_restore="always")) == 0
    with open(report) as f:
        assert json.load(f)["outcome"] == "completed"
    for t in (0, 1):
        with open(os.path.join(fleet, f"metrics_rank{t}.json")) as f:
            counters = json.load(f)["counters"]
        assert counters.get("checkpoint_ops{op=restore_striped}") == 1
        striped = counters.get("checkpoint_read_bytes{mode=striped}", 0)
        assert 0 < striped < full_bytes, (t, striped, full_bytes)
        # and the gang really exchanged stripes instead of re-reading
        assert counters.get("checkpoint_stripe_bytes{dir=recv}", 0) > 0
    res = _results(fleet)
    assert res[0]["start_step"] == 8                # resumed, not recomputed
    assert res[0]["params_crc"] == res[1]["params_crc"]


def test_fleet_distributed_jax_smoke(tmp_path):
    """Optional jax.distributed bring-up: 2 real processes form one
    2-device fleet through the compat shim (no chaos — coordinator
    rejoin after restart is deliberately out of contract).

    The shim's contract is "an upgrade, not a requirement": under heavy
    machine load the coordinator barrier can time out, in which case the
    workers degrade to warned single-process mode by design.  The run
    must still complete with bit-identical params either way; the
    2-device assertions apply only when the barrier actually formed."""
    ckpt, fleet = str(tmp_path / "ckpt"), str(tmp_path / "fleet")
    report = str(tmp_path / "report.json")
    rc = _run_supervisor(_fleet_args(ckpt, fleet, report, steps=4,
                                     distributed="jax"))
    assert rc == 0
    res = _results(fleet)
    assert res[0]["params_crc"] == res[1]["params_crc"]
    if not all(r["dist_ok"] for r in res.values()):
        pytest.skip("jax.distributed barrier timed out under load; "
                    "workers degraded to single-process as designed")
    for t, r in res.items():
        # process_count, not device_count: an inherited XLA_FLAGS may
        # force-multiply host devices — the barrier invariant is the
        # number of JOINED PROCESSES.
        assert r["process_count"] == 2, r
