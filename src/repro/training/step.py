"""Training step factory: loss, remat, microbatch accumulation, AdamW.

The returned ``train_step(params, opt_state, batch) -> (params, opt_state,
metrics)`` is a single jit-able function; the launcher wraps it in jax.jit
with in/out shardings from repro.parallel.sharding. Microbatching runs a
lax.scan over grad accumulation so the global batch is decoupled from
per-device activation memory; remat uses the dots-saveable policy (recompute
everything except matmul outputs — the standard memory/compute trade at
scale).

Nonfinite guard: every step all-reduces a FINITE flag over the loss and
every grad leaf (under jit/GSPMD the ``jnp.all`` reductions over sharded
leaves are already global collectives, so each host sees the same verdict)
and, when any value is nonfinite, keeps params/opt-state byte-identical —
a NaN burst skips a step instead of training the model into garbage.  The
host-side :class:`GradGuard` consumes the flag plus the loss each step and
escalates: a bounded budget of consecutive skips, then rollback; a
sustained loss spike above the running EMA, then rollback.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.optim import AdamWConfig, adamw_update


@dataclasses.dataclass(frozen=True)
class TrainHyper:
    optimizer: AdamWConfig = AdamWConfig()
    microbatches: int = 1
    remat: bool = False  # models remat per-layer internally
    aux_weight: float = 0.01      # MoE load-balance loss weight
    z_weight: float = 1e-4        # z-loss for logit stability


def loss_fn(forward: Callable, params: Any, batch: dict,
            aux_weight: float = 0.01, z_weight: float = 1e-4) -> tuple:
    """Next-token CE + MoE aux + z-loss. forward(params, batch)->(logits,aux).

    The label logit is extracted with a masked SUM over the vocab axis (not
    take_along_axis/gather): the mask is elementwise over the vocab-sharded
    logits, so GSPMD never all-gathers the vocab dimension — gather would
    replicate (B, S, V) f32 on every chip.
    """
    logits, aux = forward(params, batch)
    with jax.named_scope("loss"):
        labels = batch["labels"]
        T = labels.shape[1]
        logits = logits[:, -T:].astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
        at_label = jnp.sum(
            jnp.where(vocab_iota == labels[..., None], logits, 0.0), axis=-1)
        ce = (logz - at_label).mean()
        zloss = (logz ** 2).mean()
        return ce + aux_weight * aux + z_weight * zloss, (ce, aux)


def make_train_step(forward: Callable, hyper: TrainHyper) -> Callable:
    """forward(params, batch) -> (logits, aux)."""

    flc = functools.partial(loss_fn, forward, aux_weight=hyper.aux_weight,
                            z_weight=hyper.z_weight)
    if hyper.remat:
        flc = jax.checkpoint(
            flc, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    grad_fn = jax.value_and_grad(flc, has_aux=True)

    def compute_grads(params, batch):
        if hyper.microbatches == 1:
            (loss, (ce, aux)), grads = grad_fn(params, batch)
            return loss, ce, aux, grads

        mb = hyper.microbatches

        def resplit(x):
            b = x.shape[0]
            assert b % mb == 0, (b, mb)
            return x.reshape(mb, b // mb, *x.shape[1:])

        micro = jax.tree.map(resplit, batch)

        def acc_step(carry, mbatch):
            loss_a, ce_a, aux_a, g_a = carry
            (loss, (ce, aux)), g = grad_fn(params, mbatch)
            g_a = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), g_a, g)
            return (loss_a + loss, ce_a + ce, aux_a + aux, g_a), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss, ce, aux, grads), _ = jax.lax.scan(
            acc_step, (0.0, 0.0, 0.0, g0), micro)
        inv = 1.0 / mb
        return loss * inv, ce * inv, aux * inv, jax.tree.map(
            lambda g: g * inv, grads)

    def train_step(params, opt_state, batch, grad_scale=None):
        loss, ce, aux, grads = compute_grads(params, batch)
        if grad_scale is not None:
            # fault-injection hook: the chaos runtime feeds NaN here so the
            # guard below is exercised end-to-end (1.0 in normal operation)
            grads = jax.tree.map(lambda g: g * grad_scale, grads)
        finite = jnp.isfinite(loss)
        for g in jax.tree.leaves(grads):
            finite &= jnp.all(jnp.isfinite(g))
        new_params, new_opt, om = adamw_update(hyper.optimizer, params,
                                               grads, opt_state)
        # skip-step: a nonfinite loss/grad leaves params, moments AND the
        # schedule step untouched (jnp.where keeps dtypes leaf-by-leaf)
        keep = lambda new, old: jnp.where(finite, new, old)  # noqa: E731
        params = jax.tree.map(keep, new_params, params)
        opt_state = jax.tree.map(keep, new_opt, opt_state)
        metrics = {"loss": loss, "ce": ce, "aux": aux,
                   "finite": finite.astype(jnp.float32), **om}
        return params, opt_state, metrics

    return train_step


# ---------------------------------------------------------------------------
# host-side escalation: skip budget + loss-spike divergence -> rollback
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GuardPolicy:
    max_consecutive_skips: int = 3   # nonfinite steps in a row before rollback
    spike_factor: float = 3.0        # loss > factor * EMA counts as a spike
    spike_patience: int = 3          # consecutive spikes before rollback
    ema_beta: float = 0.9            # loss EMA decay
    warmup_steps: int = 5            # steps before spike detection arms


class GradGuard:
    """Consumes (loss, finite) once per step; returns the loop's action:

    ``"ok"``        update applied, loss healthy
    ``"skip"``      nonfinite step — params were not updated (in-jit
                    guard); within the consecutive-skip budget
    ``"rollback"``  skip budget exhausted, or the loss has spiked above
                    ``spike_factor`` x its EMA for ``spike_patience``
                    consecutive steps — restore the last checkpoint

    Pure host-side state so policies are unit-testable without a model;
    call :meth:`reset` after acting on a rollback.
    """

    def __init__(self, policy: GuardPolicy = GuardPolicy()):
        self.policy = policy
        self.ema: float | None = None
        self.steps = 0
        self.consecutive_skips = 0
        self.consecutive_spikes = 0
        # what caused the most recent skip/rollback — the train loop logs
        # it with the step index and it labels the gradguard_events
        # counters in the metrics registry
        self.last_trigger: str | None = None

    def update(self, loss: float, finite: bool) -> str:
        from repro.obs import REGISTRY
        p = self.policy
        if not finite or not math.isfinite(loss):
            self.consecutive_skips += 1
            if self.consecutive_skips > p.max_consecutive_skips:
                self.last_trigger = "skip_budget"
                REGISTRY.counter("gradguard_events", kind="rollback",
                                 trigger="skip_budget")
                return "rollback"
            self.last_trigger = "nonfinite"
            REGISTRY.counter("gradguard_events", kind="skip",
                             trigger="nonfinite")
            return "skip"
        self.consecutive_skips = 0
        self.steps += 1
        if self.ema is None:
            self.ema = loss
            return "ok"
        if self.steps > p.warmup_steps and loss > p.spike_factor * self.ema:
            # diverging: don't fold the spike into the EMA (that would
            # normalize the divergence it is trying to detect)
            self.consecutive_spikes += 1
            if self.consecutive_spikes >= p.spike_patience:
                self.last_trigger = "loss_spike"
                REGISTRY.counter("gradguard_events", kind="rollback",
                                 trigger="loss_spike")
                return "rollback"
            return "ok"
        self.consecutive_spikes = 0
        self.ema = p.ema_beta * self.ema + (1 - p.ema_beta) * loss
        return "ok"

    def reset(self) -> None:
        """Forget history after a rollback (the restored state's loss scale
        may differ from the diverged one's)."""
        self.ema = None
        self.steps = 0
        self.consecutive_skips = 0
        self.consecutive_spikes = 0
