"""The training driver: one loop for every ``kind: train`` mix, driving the
program's train step (``repro.training.make_train_step`` jitted with its
parameters and optimizer state donated, as ``launch/train.run`` builds it).

A mix (a ``bench/traffic/<mix>.json`` file) gives the batch, the sequence
length, the optimizer's and the loss's settings, how many first steps are
checked, and the limits of the numbers compared.

Set-up builds the step and its state once, from the seed, and drives it
through the first ``check.steps`` steps through the window's own call and
feed; the readings the check compares are taken then.  The window then
goes on with the same object.  Batches are uniform random tokens drawn on
the device from the seed, a new draw for every step.  The loss is read
every step, as the launcher does.  After the window the program's state is
dropped and the reference runs the first steps from the same weights and
batches.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

import harness

CHECKS = ("grad_norm_gap", "update_norm_gap")


def _opt_config(traffic: dict):
    from repro.optim import AdamWConfig
    return AdamWConfig(**traffic["optimizer"])


def data_fn(config: dict, traffic: dict, seed: int):
    """``batch(i)``: step i's rows, uniform random tokens below the true
    vocabulary, drawn on the device from the seed; every row differs."""
    import jax
    import jax.numpy as jnp
    B, S = traffic["batch"], traffic["seq_len"]
    vocab = config["token_vocab"]
    key = jax.random.fold_in(harness.jax_key(seed), 0x0DA7A)

    @jax.jit
    def draw(i):
        t = jax.random.randint(jax.random.fold_in(key, i), (B, S + 1), 0,
                               vocab, jnp.int32)
        return {"tokens": t[:, :-1], "labels": t[:, 1:]}

    return lambda i: draw(np.int32(i))


def build(cell, seed: int):
    """The program's jitted step, its state from the seed, and the feed."""
    import jax
    from repro import training
    from repro.optim import adamw_init

    fam = cell.family()
    bundle = fam.program_bundle(cell.config)
    hyper = training.TrainHyper(
        optimizer=_opt_config(cell.traffic),
        z_weight=cell.traffic["loss"]["z_weight"])
    step = jax.jit(training.make_train_step(bundle.forward, hyper),
                   donate_argnums=(0, 1))
    params = fam.make_params(bundle, harness.jax_key(seed))
    opt = jax.jit(adamw_init)(params)
    return fam, bundle, step, params, opt, data_fn(cell.config, cell.traffic,
                                                   seed)


def _leaf_norms(tree) -> dict:
    import jax
    import jax.numpy as jnp
    norms = jax.jit(lambda t: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))(
            tree)
    return {jax.tree_util.keystr(k): float(v) for k, v in
            jax.tree_util.tree_flatten_with_path(norms)[0]}


def first_steps(cell, fam, bundle, step, params, opt, batch, seed: int):
    """Drive the step through the first ``check.steps`` steps; returns the
    state and the program's readings: each step's loss, each leaf's norm of
    the first gradient as the optimizer holds it (mu / (1 - b1) after one
    step) and of the change of the parameters after the last of them."""
    import jax
    n = cell.traffic["check"]["steps"]
    b1 = cell.traffic["optimizer"]["b1"]
    losses, grad_norms = [], None
    for i in range(1, n + 1):
        params, opt, m = step(params, opt, batch(i))
        losses.append(float(m["loss"]))
        if i == 1:
            grad_norms = {k: v / (1 - b1)
                          for k, v in _leaf_norms(opt["mu"]).items()}
    p0 = fam.make_params(bundle, harness.jax_key(seed))
    change = _leaf_norms(jax.tree.map(lambda a, b: a - b, params, p0))
    del p0
    return params, opt, {"losses": losses, "grad_norms": grad_norms,
                         "change_norms": change}


def reference_readings(cell, seed: int, dtype=None) -> dict:
    """The reference's readings from the same weights and batches."""
    import jax.numpy as jnp
    fam = cell.family()
    ref = cell.reference()
    bundle = fam.program_bundle(cell.config)
    params = fam.make_params(bundle, harness.jax_key(seed))
    batch = data_fn(cell.config, cell.traffic, seed)
    steps = range(1, cell.traffic["check"]["steps"] + 1)
    batches = [(b["tokens"], b["labels"]) for b in map(batch, steps)]
    return ref.train_readings(cell.config, params, batches,
                              cell.traffic["optimizer"],
                              cell.traffic["loss"]["z_weight"],
                              dtype=dtype or jnp.float32)


def gaps(prog: dict, ref: dict) -> dict:
    """The widest relative gap of a step's loss (read, not compared: no
    fault or control reads far enough above sound runs), and the numbers
    compared: the widest gap of a leaf's norm (first gradient; change
    after the checked steps), each gap between the two norms measured
    against the larger of the reference's norm of that leaf and of the
    median leaf.  Leaves whose reference gradient is under a thousandth of
    the median leaf's are left out of the change."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                   ref["losses"]))
    g_ref = ref["grad_norms"]
    g_med = float(np.median(list(g_ref.values())))
    keep = [k for k in g_ref if g_ref[k] >= 1e-3 * g_med]

    def worst(p, r, keys):
        med = float(np.median([r[k] for k in keys]))
        return max(abs(p[k] - r[k]) / max(r[k], med) for k in keys)

    return {"loss_gap": loss,
            "grad_norm_gap": worst(prog["grad_norms"], g_ref, list(g_ref)),
            "update_norm_gap": worst(prog["change_norms"],
                                     ref["change_norms"], keep)}


def window(cell, step, params, opt, batch, seconds: float, trace: bool,
           log) -> dict:
    """Train for ``seconds``: every step that begins in the window runs to
    its end, and its loss is read."""
    counter = harness.CompileCounter()
    ann = harness.annotator(trace)
    prof = harness.Profiler(cell.root, cell.name) if trace else None
    trace_at = seconds / 3.0
    trace_len = min(cell.traffic.get("trace_seconds", 4.0), seconds / 3.0)
    clock = time.monotonic
    i = cell.traffic["check"]["steps"]
    spans, losses, skipped = [], [], 0
    t0 = clock()
    counter.armed = True
    while True:
        t = clock() - t0
        if t >= seconds:
            break
        if prof is not None and not prof.active and prof.t0 is None \
                and t >= trace_at:
            prof.start(lambda: clock() - t0)
        if prof is not None and prof.active and t >= trace_at + trace_len:
            prof.stop(lambda: clock() - t0)
        i += 1
        with ann("next_batch"):
            b = batch(i)
        with ann("train_step"):
            params, opt, m = step(params, opt, b)
            loss = float(m["loss"])
            skipped += float(m["finite"]) == 0.0
        spans.append((t, clock() - t0))
        losses.append(loss)
    if prof is not None and prof.active:
        prof.stop(lambda: clock() - t0)
    counter.armed = False
    log(f"[window] compilations inside the window: {counter.count} "
        f"({counter.seconds:.3f} s)")
    tokens = cell.traffic["batch"] * cell.traffic["seq_len"]
    span = spans[-1][1] - spans[0][0]
    out = {"steps": len(spans), "failed": int(skipped),
           "compiles": counter.count, "tokens_per_step": tokens,
           "tokens_per_s": len(spans) * tokens / span}
    log(f"[window] {len(spans)} steps in {span:.3f} s: "
        f"{out['tokens_per_s']:.1f} tokens/s; step ms p50 "
        f"{1e3 * harness.percentile([e - s for s, e in spans], 50):.2f}; "
        f"loss {losses[0]:.4f} -> {losses[-1]:.4f}, {skipped} skipped")
    if prof is not None and prof.t0 is not None:
        inside = [(s, e) for s, e in spans if s >= prof.t0 and e <= prof.t1]
        out["layer_ctx"] = {
            "xplane": prof.xplane(), "steps_traced": inside,
            "tokens_per_step": tokens}
    return out


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        devices, log) -> tuple:
    """One training run; returns (metrics, attempted, failed, checks,
    device, per-layer context or None)."""
    if trace:
        import repro.obs as robs
        robs.enable(process_name="bench")
    fam, bundle, step, params, opt, batch = build(cell, seed)
    params, opt, prog = first_steps(cell, fam, bundle, step, params, opt,
                                    batch, seed)
    log(f"[setup] first {len(prog['losses'])} steps, losses "
        f"{prog['losses']}")
    setup_s = time.monotonic() - t_start
    w = window(cell, step, params, opt, batch, seconds, trace, log)
    del params, opt                 # donated to the window's first step
    dev = harness.device_info(devices)
    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    metrics = {k: {"value": v, "unit": units[k]} for k, v in
               {"setup_s": setup_s,
                "train_tokens_per_s": w["tokens_per_s"]}.items()
               if k in units}
    layer_ctx = w.get("layer_ctx")
    if layer_ctx is not None:
        layer_ctx.update(config=cell.config, family=fam, traffic=cell.traffic)
    gc.collect()
    checks = check(cell, seed, prog, log)
    return metrics, w["steps"], w["failed"], checks, dev, layer_ctx


def check(cell, seed: int, prog: dict, log) -> dict:
    t = time.monotonic()
    ref = reference_readings(cell, seed)
    g = gaps(prog, ref)
    log(f"[check] reference over {len(ref['losses'])} steps took "
        f"{time.monotonic() - t:.1f} s; losses program {prog['losses']} "
        f"reference {ref['losses']}, widest relative gap {g['loss_gap']}")
    limits = cell.traffic["check"]["limits"]
    return {k: {"value": g[k], "limit": limits[k]} for k in CHECKS}


def checks_pass(checks: dict) -> bool:
    return all(c["value"] is not None and math.isfinite(c["value"])
               and c["value"] <= c["limit"] for c in checks.values())
