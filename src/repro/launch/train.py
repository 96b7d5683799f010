"""Training launcher: a self-healing train loop over the full runtime.

The loop is an explicit recovery state machine — every transition below is
exercised by injected faults (``repro.runtime.chaos``) in tests and CI,
not assumed::

            +--------------------- RUN ----------------------+
            | step -> heartbeat -> monitor.check -> guard    |
            +--+----------------+----------------------+-----+
               | host dead /    | guard: "rollback"    | guard: "skip"
               | straggler      | (skip budget blown   | (nonfinite grad;
               v                |  or loss spike)      |  params untouched
            REMESH              v                      |  by the in-jit
            plan_elastic_    RESTORE                   |  finite guard)
            remesh over      newest INTACT checkpoint  |
            survivors  --->  (CRC-verified, falls  ----+--> back to RUN
            re-shard         back past corrupt steps),
            data + params    rewind step counter

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --smoke \
        --steps 20 --ckpt-dir /tmp/ckpt
    # fault drills: die at step 12, NaN burst at 5, corrupt the step-10 save
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --steps 20 \
        --ckpt-dir /tmp/ckpt --ckpt-every 5 --chaos kill@12 --chaos nan@5

Integrates host-sharded synthetic data with prefetch (step-indexed, so a
restart or an elastic re-shard replays the exact global batches), a jit'd
train step with the production shardings and an all-reduced finite flag,
async CRC-committed checkpointing with restart discovery, and a simulated
multi-host fleet (``n_hosts``): peer heartbeats are driven synthetically
on a per-step virtual clock so silence/straggler chaos is deterministic,
while host 0's compute is real.  In a real pod the peers are processes and
the mesh is rebuilt from survivors; here the device set is this
container's and ``sharding_fn`` re-places restored state onto it — the
elastic interfaces (plan, re-shard, step-indexed data resume) are the same.

Worker mode (``--process-id R --num-processes W``, launched by
``repro.launch.supervisor``): this process is rank R of a real W-process
fleet.  Each rank computes the identical full global batch (deterministic
redundancy — no cross-process collectives, so a CPU fleet works and
params stay bit-identical across ranks, which the result files prove via
``tree_fingerprint``), publishes per-step heartbeat files the supervisor
watches, dies with exit status 43 on an injected kill, and on a gang
restart optionally restores STRIPED: each rank reads 1/W of the shard
bytes and all-gathers the rest from peers over loopback TCP
(``--stripe-ports``).  ``--total-steps`` gives the run's global horizon
so a restarted worker resumes from its checkpoint and stops at the same
step the uninterrupted run would — the bit-identical-resume contract.
``--distributed jax`` additionally brings up ``jax.distributed`` via the
version-compat shim (optional: coordinator rejoin after a mid-run worker
restart is not reliable across jax versions, so supervision never
depends on it).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.obs as obs
from repro.checkpoint import CheckpointManager
from repro.configs import get_bundle
from repro.data import DataConfig, make_train_iterator
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import (make_host_mesh, make_local_mesh,
                               make_production_mesh, make_worker_mesh)
from repro.optim import AdamWConfig, adamw_init
from repro.parallel.sharding import param_specs
from repro.runtime import (ChaosInjector, ChaosKilled, FleetWorker,
                           HeartbeatMonitor, StragglerPolicy, compat,
                           plan_elastic_remesh, tree_fingerprint)
from repro.training import GradGuard, GuardPolicy, TrainHyper, make_train_step


def run(arch: str, *, smoke: bool = True, steps: int = 20,
        seq_len: int = 128, global_batch: int = 8, mesh_kind: str = "local",
        ckpt_dir: str | None = None, ckpt_every: int = 10,
        microbatches: int = 1, lr: float = 3e-4,
        log_every: int = 1, chaos=None, chaos_seed: int = 0,
        n_hosts: int = 1, hb_timeout_steps: float | None = None,
        straggler_factor: float | None = None,
        straggler_patience: int | None = None,
        guard_policy: GuardPolicy | None = None,
        max_recoveries: int = 8, trace_out: str | None = None,
        metrics_out: str | None = None, telemetry=None,
        fleet: FleetWorker | None = None,
        total_steps: int | None = None,
        n_layers: int | None = None) -> dict:
    """Train ``arch`` for ``steps`` steps under the recovery state machine
    (module docstring).  ``n_layers`` cuts the depth, widths unchanged,
    to what the mesh holds."""
    if chaos is not None and not isinstance(chaos, ChaosInjector):
        chaos = ChaosInjector(chaos, seed=chaos_seed)
    if fleet is not None and fleet.distributed == "jax" and fleet.coordinator:
        # must run before any other jax call (backend init is sticky)
        fleet.dist_ok = compat.distributed_initialize(
            fleet.coordinator, fleet.num_processes, fleet.process_id)
    bundle = get_bundle(arch, smoke=smoke)
    if n_layers is not None:
        bundle = dataclasses.replace(
            bundle, cfg=dataclasses.replace(bundle.cfg, n_layers=n_layers))
    if fleet is not None:
        mesh = make_worker_mesh()
    else:
        mesh = {"local": make_local_mesh,
                "host": make_host_mesh,
                "single": make_production_mesh,
                "multi": lambda: make_production_mesh(multi_pod=True)
                }[mesh_kind]()
    # captured while the (optional) distributed backend is known-alive;
    # with jax.distributed up these are GLOBAL counts (process_count == 1
    # means the barrier never formed; device_count additionally scales
    # with any forced host-platform device multiplicity)
    n_devices = jax.device_count()
    n_procs = jax.process_count()

    pspecs = param_specs(bundle.kind, bundle.abstract_params(), mesh)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                       is_leaf=lambda x: isinstance(x, P))
    tree_sh = {"params": psh,
               "opt": {"mu": psh, "nu": psh,
                       "step": NamedSharding(mesh, P())}}
    # built in place on the mesh: an eager init would hold the whole f32
    # draw and both f32 Adam moments on one device first
    params = jax.jit(bundle.init_params, out_shardings=psh)(
        jax.random.PRNGKey(0))
    opt = jax.jit(adamw_init, out_shardings=tree_sh["opt"])(params)

    def sharding_fn(tree):
        """Elastic re-shard: place a restored host tree onto whatever mesh
        this process currently drives."""
        return jax.device_put(tree, tree_sh)

    vocab = getattr(bundle.cfg, "vocab")
    data_cfg = DataConfig(vocab=vocab, seq_len=seq_len,
                          global_batch=global_batch)

    start_step = 0
    mgr = None
    exchange = None
    # with replicated fleet compute every rank holds identical state, so
    # rank 0 alone writes checkpoints (it is host 0, the manifest writer);
    # every rank restores from the shared dir
    can_save = fleet is None or fleet.process_id == 0
    if ckpt_dir:
        mgr = CheckpointManager(
            ckpt_dir,
            fault_hook=chaos.checkpoint_write_hook if chaos is not None
            and can_save else None)
        stripe = None
        if fleet is not None and fleet.striped_restore:
            # collective striped restore: valid only on a gang start where
            # every rank reaches this point (the supervisor guarantees it
            # by passing --striped-restore to whole gangs only)
            exchange = fleet.make_exchange()
            if exchange is not None:
                stripe = (fleet.process_id, fleet.num_processes, exchange)
        restored = mgr.restore({"params": params, "opt": opt},
                               sharding_fn=sharding_fn, stripe=stripe)
        if restored is not None:
            start_step, tree = restored
            params, opt = tree["params"], tree["opt"]
            print(f"[train] restored step {start_step} from {ckpt_dir}"
                  f"{' (striped)' if stripe else ''}")

    # the LR schedule spans the run's GLOBAL horizon (restored start +
    # remaining steps), so a crash-restarted run rebuilds the exact
    # schedule the uninterrupted run used — bit-identical resume depends
    # on it (a schedule over "steps remaining" would diverge post-warmup).
    # `total_steps` (the supervisor's fixed horizon) pins that endpoint
    # explicitly so a restarted worker stops where the uninterrupted run
    # would, instead of running `steps` more from wherever it restored.
    end_step = max(total_steps, start_step) if total_steps is not None \
        else start_step + steps
    hyper = TrainHyper(optimizer=AdamWConfig(
        lr=lr, warmup_steps=5, total_steps=max(end_step, 10)),
        microbatches=microbatches)
    step_fn = make_train_step(bundle.forward, hyper)
    jit_step = jax.jit(step_fn, donate_argnums=(0, 1))

    # -- simulated fleet: host 0 is this process; peers heartbeat on a
    # per-step virtual clock so chaos silence/slowness is deterministic
    host_id, rank, n_data_hosts = 0, 0, n_hosts
    assert global_batch % n_hosts == 0, (global_batch, n_hosts)
    vclock = [0.0]
    # telemetry traces the recovery state machine ON THE VIRTUAL CLOCK, so
    # a chaos scenario replays with bit-identical span timestamps (the
    # determinism test diffs two exported traces); installed globally so
    # GradGuard/checkpoint/kernel events land in the same registry
    tel = telemetry
    if tel is None:
        if trace_out or metrics_out:
            tel = obs.enable(clock=lambda: vclock[0], process_name="train")
        else:
            tel = obs.get_telemetry()
    monitor = HeartbeatMonitor(
        list(range(n_hosts)),
        StragglerPolicy.from_env(
            heartbeat_timeout_s=hb_timeout_steps,
            straggler_factor=straggler_factor,
            patience=straggler_patience,
            default=StragglerPolicy(heartbeat_timeout_s=4.0,
                                    straggler_factor=2.0, patience=3)),
        clock=lambda: vclock[0])
    guard = GradGuard(guard_policy or GuardPolicy())

    def make_extras(per_host_batch: int) -> dict:
        extras = {}
        if bundle.kind == "audio":
            extras["frames"] = np.zeros(
                (per_host_batch, bundle.cfg.n_audio_ctx, bundle.cfg.d_model),
                np.float32)
        if bundle.kind == "vlm":
            extras["vision"] = np.zeros(
                (per_host_batch, bundle.cfg.vision_tokens,
                 bundle.cfg.d_model), np.float32)
        return extras

    it = make_train_iterator(data_cfg, host_id=rank, n_hosts=n_data_hosts,
                             start_step=start_step)
    extras = make_extras(global_batch // n_data_hosts)

    history, grad_norms, step_log, events = [], [], [], []
    i = start_step
    recoveries = 0
    last_saved = start_step if mgr else None

    def ckpt_wait(at_step: int) -> bool:
        """Land the in-flight async save; a FAILED WRITE (e.g. chaos
        diskfull -> ENOSPC) is an event, never a crash — a full disk
        costs recovery-point age, not the run."""
        try:
            mgr.wait()
            return True
        except OSError as e:
            events.append({"kind": "ckpt_save_failed", "step": at_step,
                           "error": str(e)})
            print(f"[train] checkpoint save failed ({e}); continuing")
            return False

    def restore_or_keep(reason: str, at_step: int) -> int:
        """RESTORE state: rewind to the newest intact checkpoint (the
        manager walks past corrupt ones); with nothing restorable, keep
        the current (guarded) state and continue forward."""
        nonlocal params, opt
        with tel.span("RESTORE", step=at_step, reason=reason):
            if mgr is None:
                events.append({"kind": "rollback_unavailable",
                               "step": at_step, "reason": reason})
                return at_step
            ckpt_wait(at_step)
            restored = mgr.restore({"params": params, "opt": opt},
                                   sharding_fn=sharding_fn)
            if restored is None:
                events.append({"kind": "rollback_unavailable",
                               "step": at_step, "reason": reason})
                return at_step
            rstep, tree = restored
            params, opt = tree["params"], tree["opt"]
            events.append({"kind": "restore", "step": at_step,
                           "restored_step": rstep, "reason": reason})
            print(f"[train] {reason} at step {at_step}: restored checkpoint "
                  f"step {rstep}")
            return rstep

    fired_seen = len(chaos.fired) if chaos is not None else 0

    def drain_chaos_instants(at_step: int) -> None:
        """Mirror newly-fired chaos events into the trace as instants."""
        nonlocal fired_seen
        if chaos is None or not tel.enabled:
            return
        for ev in chaos.fired[fired_seen:]:
            tel.instant("chaos", cat="chaos", event=str(ev), step=at_step)
        fired_seen = len(chaos.fired)

    def reopen_data(at_step: int) -> None:
        nonlocal it, extras
        it.close()
        it = make_train_iterator(data_cfg, host_id=rank,
                                 n_hosts=n_data_hosts, start_step=at_step)
        extras = make_extras(global_batch // n_data_hosts)

    run_span = tel.begin("RUN", cat="state", step=i) if tel.enabled else None
    try:
        with compat.set_mesh(mesh):
            while i < end_step:
                vclock[0] += 1.0
                if fleet is not None and not (
                        chaos is not None
                        and chaos.partitioned(i, fleet.process_id)):
                    fleet.heartbeat(i)
                if chaos is not None:
                    try:
                        # raises ChaosKilled (exit 43); fleet workers die
                        # only when the spec targets their rank
                        chaos.maybe_kill(
                            i, rank=fleet.process_id if fleet else None)
                    except ChaosKilled:
                        # preemption grace (SIGTERM-style): an in-flight
                        # async save lands before death, so "the last
                        # completed checkpoint" is a deterministic notion.
                        # NOTHING here may displace the kill — a pending
                        # save error surfacing now would turn exit 43
                        # into exit 1 and the supervisor would misread
                        # chaos as a crash
                        if mgr:
                            try:
                                mgr.wait()
                            except Exception:
                                pass
                        raise

                t0 = time.time()
                idx, batch = it.next()
                assert idx == i, (idx, i)
                batch = {**batch, **extras}
                gs = np.float32(chaos.grad_scale(i)) if chaos is not None \
                    else np.float32(1.0)
                params, opt, metrics = jit_step(params, opt, batch, gs)
                loss = float(metrics["loss"])
                finite = bool(float(metrics["finite"]) > 0.0)
                dt = time.time() - t0

                # heartbeats: ours is real; simulated peers echo our step
                # time unless chaos silences or slows them
                for h in monitor.alive_hosts():
                    if chaos is not None:
                        if chaos.heartbeat_silenced(h, i):
                            continue
                        monitor.heartbeat(
                            h, dt * chaos.step_time_factor(h, i))
                    else:
                        monitor.heartbeat(h, dt)
                failed = monitor.check()
                action = guard.update(loss, finite)
                drain_chaos_instants(i)
                if tel.enabled:
                    tel.metrics.observe("train_step_s", dt)

                history.append(loss)
                grad_norms.append(float(metrics["grad_norm"]))
                step_log.append(i)
                if i % log_every == 0:
                    flag = "" if finite else "  [nonfinite->skipped]"
                    print(f"[train] step {i} loss {loss:.4f} "
                          f"({dt*1e3:.0f} ms){flag}")

                if failed:
                    # FAULT -> RESTORE -> REMESH: stop, restore the newest
                    # intact checkpoint, re-plan the mesh over survivors,
                    # re-shard params/opt and the step-indexed data stream
                    recoveries += 1
                    if recoveries > max_recoveries:
                        raise RuntimeError("recovery limit exceeded")
                    tel.finish(run_span, end_step=i, reason="host_failure")
                    run_span = None
                    with tel.span("REMESH", cat="state", step=i,
                                  failed=str(failed)):
                        survivors = monitor.alive_hosts()
                        if host_id not in survivors:
                            raise RuntimeError(
                                f"host {host_id} was evicted")
                        plan = plan_elastic_remesh(survivors,
                                                   chips_per_host=1,
                                                   model_parallel=1)
                        rank = plan.host_ranks[host_id]
                        n_data_hosts = plan.n_hosts
                        assert global_batch % n_data_hosts == 0, \
                            (global_batch, n_data_hosts)
                        events.append({"kind": "remesh", "step": i,
                                       "failed": failed,
                                       "survivors": survivors,
                                       "plan": dataclasses.asdict(plan)})
                        print(f"[train] hosts {failed} failed at step {i}; "
                              f"remesh over {survivors} "
                              f"(dp={plan.data_parallel})")
                    i = restore_or_keep("host failure", i)
                    reopen_data(i)
                    guard.reset()
                    if tel.enabled:
                        run_span = tel.begin("RUN", cat="state", step=i)
                    continue

                if action == "rollback":
                    recoveries += 1
                    if recoveries > max_recoveries:
                        raise RuntimeError("recovery limit exceeded")
                    print(f"[guard] step {i}: rollback "
                          f"(trigger={guard.last_trigger})")
                    tel.instant("guard_rollback", cat="guard", step=i,
                                trigger=guard.last_trigger)
                    tel.finish(run_span, end_step=i, reason="divergence")
                    run_span = None
                    i = restore_or_keep("divergence", i)
                    reopen_data(i)
                    guard.reset()
                    if tel.enabled:
                        run_span = tel.begin("RUN", cat="state", step=i)
                    continue

                if action == "skip":
                    print(f"[guard] step {i}: skip "
                          f"(trigger={guard.last_trigger}, consecutive="
                          f"{guard.consecutive_skips})")
                    tel.instant("guard_skip", cat="guard", step=i,
                                trigger=guard.last_trigger)
                    events.append({"kind": "skip", "step": i})

                if mgr and can_save and (i + 1) % ckpt_every == 0:
                    ckpt_wait(i)   # surface a prior failed write first
                    mgr.save_async(i + 1, {"params": params, "opt": opt})
                    last_saved = i + 1
                    if chaos is not None and chaos.wants_corrupt(i + 1):
                        if ckpt_wait(i + 1):   # land it, then damage it
                            chaos.maybe_corrupt(ckpt_dir, i + 1)
                i += 1
            if mgr and can_save:
                final_ok = ckpt_wait(end_step)
                if last_saved != end_step or not final_ok:
                    mgr.save_async(end_step,
                                   {"params": params, "opt": opt})
                    ckpt_wait(end_step)
    finally:
        # teardown must never displace an in-flight ChaosKilled (exit 43 is
        # the supervisor's restart signal) — every item is individually
        # contained
        for teardown in (it.close,
                         lambda: drain_chaos_instants(i),
                         lambda: tel.finish(run_span, end_step=i),
                         # artifacts land even when a chaos kill unwinds
                         # the loop — the restart inspects the dead run's
                         # trace
                         lambda: trace_out and tel.write_trace(trace_out),
                         lambda: metrics_out
                         and tel.write_metrics(metrics_out),
                         lambda: exchange and exchange.close(),
                         lambda: fleet is not None and fleet.dist_ok
                         and compat.distributed_shutdown()):
            try:
                teardown()
            except Exception as e:
                print(f"[train] teardown error (ignored): {e!r}")
    if fleet is not None:
        fleet.write_result({
            "params_crc": tree_fingerprint({"params": params, "opt": opt}),
            "first_loss": history[0] if history else None,
            "final_loss": history[-1] if history else None,
            "start_step": start_step, "end_step": end_step,
            "dist_ok": fleet.dist_ok,
            "device_count": n_devices,
            "process_count": n_procs,
        })
    return {"losses": history, "grad_norms": grad_norms, "steps": step_log,
            "events": events,
            "params": params, "opt": opt,
            "telemetry": tel.snapshot() if tel.enabled else None}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--mesh", default="local",
                    choices=["local", "host", "single", "multi"],
                    help="local: one device; host: (1, n) over this "
                         "host's n devices; single/multi: the 16x16 "
                         "production pod meshes")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--chaos", action="append", default=None,
                    metavar="SPEC",
                    help="inject a fault (repeatable): kill@N, nan@N, "
                         "silence@N:host=H, slow@N:host=H,factor=F, "
                         "corrupt@N:mode=flip|truncate, diskfull@N, "
                         "partition@N:host=H (sigkill@N:host=H is "
                         "supervisor-side; see repro.launch.supervisor)")
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--n-hosts", type=int, default=1,
                    help="simulated fleet size (peers heartbeat "
                         "synthetically; host 0 is this process)")
    ap.add_argument("--hb-timeout-steps", type=float, default=None,
                    help="heartbeat timeout in virtual steps (default 4; "
                         "env REPRO_HEARTBEAT_TIMEOUT)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace JSON (perfetto-loadable) "
                         "of the RUN/REMESH/RESTORE state machine")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics-registry snapshot as JSON")
    # -- real-fleet worker mode (passed by repro.launch.supervisor) --------
    ap.add_argument("--process-id", type=int, default=0, metavar="R")
    ap.add_argument("--num-processes", type=int, default=None, metavar="W",
                    help="run as rank R of a W-process fleet")
    ap.add_argument("--coordinator", default=None, metavar="HOST:PORT")
    ap.add_argument("--fleet-dir", default=None, metavar="DIR",
                    help="shared dir for heartbeat files")
    ap.add_argument("--fleet-tag", type=int, default=None,
                    help="stable worker id across re-mesh renumbering")
    ap.add_argument("--stripe-ports", default=None, metavar="P0,P1,...",
                    help="per-rank TCP ports for striped restore")
    ap.add_argument("--striped-restore", action="store_true")
    ap.add_argument("--distributed", default="none",
                    choices=["none", "jax"])
    ap.add_argument("--result-out", default=None, metavar="PATH")
    ap.add_argument("--total-steps", type=int, default=None,
                    help="global step horizon (restart-safe endpoint); "
                         "overrides --steps counting from the restore")
    a = ap.parse_args()
    enable_compile_cache()
    fleet = None
    if a.num_processes is not None:
        ports = tuple(int(p) for p in a.stripe_ports.split(",")) \
            if a.stripe_ports else ()
        fleet = FleetWorker(process_id=a.process_id,
                            num_processes=a.num_processes,
                            fleet_dir=a.fleet_dir, tag=a.fleet_tag,
                            coordinator=a.coordinator, stripe_ports=ports,
                            striped_restore=a.striped_restore,
                            distributed=a.distributed,
                            result_out=a.result_out)
    try:
        out = run(a.arch, smoke=a.smoke, steps=a.steps, seq_len=a.seq_len,
                  global_batch=a.global_batch, mesh_kind=a.mesh,
                  ckpt_dir=a.ckpt_dir, ckpt_every=a.ckpt_every,
                  microbatches=a.microbatches, lr=a.lr, chaos=a.chaos,
                  chaos_seed=a.chaos_seed, n_hosts=a.n_hosts,
                  hb_timeout_steps=a.hb_timeout_steps,
                  trace_out=a.trace_out, metrics_out=a.metrics_out,
                  fleet=fleet, total_steps=a.total_steps)
    except ChaosKilled as e:
        # belt-and-braces: ChaosKilled IS a SystemExit(43), but anything
        # that re-wrapped it on the way up must not change the status the
        # supervisor keys its restart policy on
        raise SystemExit(e.code)
    losses = out["losses"]
    if losses:
        print(f"[train] done: first loss {losses[0]:.4f}, "
              f"last loss {losses[-1]:.4f}, "
              f"{len(out['events'])} fault events")
    else:
        # a restarted worker can restore AT the horizon: nothing to do
        # is success, not a crash
        print("[train] done: horizon already reached at restore; no steps")


if __name__ == "__main__":
    main()
