"""Architecture bundles: one uniform interface over all model families.

A bundle wires a model family (transformer / mamba2 / recurrentgemma /
whisper) to the launcher, dry-run, trainer and server:

  * ``abstract_params()``        — ShapeDtypeStruct tree (no allocation)
  * ``forward(params, batch)``   — training forward, (logits, aux)
  * ``prefill/decode_step``      — serving steps
  * ``input_specs(shape)``       — ShapeDtypeStruct batch for a named shape
  * ``step_kind(shape)``         — which step function the shape lowers
  * ``supports(shape)``          — long_500k only for sub-quadratic archs

SHAPES (assignment): train_4k (4096 x 256, train_step), prefill_32k
(32768 x 32, prefill), decode_32k (one token, 32k cache, batch 128),
long_500k (one token, 524288 context, batch 1; SSM/hybrid only).
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp

SHAPES = {
    "train_4k": dict(seq_len=4096, global_batch=256, kind="train"),
    "prefill_32k": dict(seq_len=32768, global_batch=32, kind="prefill"),
    "decode_32k": dict(seq_len=32768, global_batch=128, kind="decode"),
    "long_500k": dict(seq_len=524288, global_batch=1, kind="decode"),
}


# ---------------------------------------------------------------------------
# Context-parallel ring-attention policy (§Perf B6)
#
# This replaces the old mutable ``models.layers.RING_PPERMUTE`` module
# global: callers resolve a policy here (explicit argument > REPRO_RING_ATTN
# env > default) instead of monkeypatching module state, so tests and
# benchmarks can pick a path per call or per process.
# ---------------------------------------------------------------------------

RING_MODES = ("auto", "ring", "replicated", "off")


@dataclasses.dataclass(frozen=True)
class RingAttnPolicy:
    """How ``models.layers.attention`` distributes long sequences over the
    ``model`` mesh axis.

    mode:
      * ``auto``       — ppermute ring (memory-flat custom VJP) for long
        sequences, replicated-k/v shard_map below ``seq_threshold`` (the
        XLA fallback: short sequences don't amortize the hop latency);
      * ``ring``       — always the ring when shapes divide;
      * ``replicated`` — always the replicated-k/v shard_map (§Perf B5);
      * ``off``        — neither; GSPMD constraint-based layout only.

    ``max_seq_per_device`` caps the ring shard: above it the per-hop
    (S/m x S/m) score tile outgrows the blocked XLA path's q-chunked
    tiles, so ``auto`` falls back to the replicated path."""
    mode: str = "auto"
    seq_threshold: int = 4096
    max_seq_per_device: int = 4096


DEFAULT_RING_POLICY = RingAttnPolicy()


def ring_attn_policy(mode_override: str | None = None) -> RingAttnPolicy:
    """Resolve the active ring policy.  Precedence: explicit
    ``mode_override`` (e.g. ``TransformerConfig.ring_attn`` or a test's
    keyword) > ``REPRO_RING_ATTN`` env var > ``DEFAULT_RING_POLICY``.
    ``REPRO_RING_ATTN_THRESHOLD`` / ``REPRO_RING_ATTN_MAX_SHARD`` tune the
    ``auto`` thresholds from the environment."""
    mode = (mode_override or os.environ.get("REPRO_RING_ATTN")
            or DEFAULT_RING_POLICY.mode)
    if mode not in RING_MODES:
        raise ValueError(f"ring-attention mode {mode!r} not in {RING_MODES}")
    thr = int(os.environ.get("REPRO_RING_ATTN_THRESHOLD",
                             DEFAULT_RING_POLICY.seq_threshold))
    cap = int(os.environ.get("REPRO_RING_ATTN_MAX_SHARD",
                             DEFAULT_RING_POLICY.max_seq_per_device))
    return RingAttnPolicy(mode=mode, seq_threshold=thr,
                          max_seq_per_device=cap)


def decide_ring(policy: RingAttnPolicy, *, seq_len: int,
                ring_size: int) -> str:
    """Pick the context-parallel mode for a global sequence of
    ``seq_len`` on a ``ring_size``-wide model axis: 'ring', 'replicated'
    or 'off'."""
    if policy.mode != "auto":
        return policy.mode
    if (seq_len >= policy.seq_threshold
            and seq_len // ring_size <= policy.max_seq_per_device):
        return "ring"
    return "replicated"


# ---------------------------------------------------------------------------
# Trainable flash-attention policy (the fused Pallas fwd+bwd kernels)
#
# Mirrors RingAttnPolicy: callers resolve a policy (explicit argument >
# REPRO_FLASH_ATTN env > default) instead of flag-flipping module state.
# The ring policy decides HOW long sequences distribute over the mesh;
# this one decides WHICH score-tile engine runs the local fold — the
# Pallas trainable kernel (custom-VJP fwd+bwd, pruned grid) or the XLA
# einsum paths.
# ---------------------------------------------------------------------------

FLASH_MODES = ("auto", "pallas", "xla")


@dataclasses.dataclass(frozen=True)
class FlashAttnPolicy:
    """Which attention engine ``models.layers.attention`` dispatches to.

    mode:
      * ``auto``   — the trainable Pallas kernel on TPU for sequences at
        least ``min_seq`` long (below it the XLA full-mask path wins on
        launch overhead); the XLA paths on CPU/GPU backends, where Pallas
        would run in interpret mode — an emulator, not an engine.
      * ``pallas`` — always the trainable kernel (interpret mode off-TPU;
        what the grad-equality tests and the microbench pin).
      * ``xla``    — never; the pre-existing einsum/blocked paths.
    """
    mode: str = "auto"
    min_seq: int = 1024


DEFAULT_FLASH_POLICY = FlashAttnPolicy()


def flash_attn_policy(mode_override: str | None = None) -> FlashAttnPolicy:
    """Resolve the active flash-attention policy.  Precedence: explicit
    ``mode_override`` (e.g. ``TransformerConfig.attn_impl``) >
    ``REPRO_FLASH_ATTN`` env var > default; ``REPRO_FLASH_ATTN_MIN_SEQ``
    tunes the ``auto`` threshold."""
    mode = (mode_override or os.environ.get("REPRO_FLASH_ATTN")
            or DEFAULT_FLASH_POLICY.mode)
    if mode not in FLASH_MODES:
        raise ValueError(f"flash-attention mode {mode!r} not in "
                         f"{FLASH_MODES}")
    ms = int(os.environ.get("REPRO_FLASH_ATTN_MIN_SEQ",
                            DEFAULT_FLASH_POLICY.min_seq))
    return FlashAttnPolicy(mode=mode, min_seq=ms)


def decide_flash(policy: FlashAttnPolicy, *, seq_len: int, kv_len: int,
                 on_tpu: bool) -> str:
    """'pallas' (the trainable fused kernel) or 'xla' for one attention
    call.  ``auto`` requires a real Mosaic backend and a sequence long
    enough to amortize kernel launch + pair-table prefetch."""
    if policy.mode != "auto":
        return policy.mode
    if on_tpu and max(seq_len, kv_len) >= policy.min_seq:
        return "pallas"
    return "xla"


@dataclasses.dataclass
class ArchBundle:
    arch_id: str
    kind: str                   # dense | moe | vlm | ssm | audio | hybrid
    cfg: Any
    family: Any                 # model module
    sub_quadratic: bool = False
    kv_dtype_decode: Any = None  # e.g. jnp.int8 for big dense decode
    extras: dict = dataclasses.field(default_factory=dict)
    # the device mesh the training forward shards over (``on_mesh``);
    # None: whatever mesh the caller has entered, if any
    mesh: Any = None

    # -- params ------------------------------------------------------------
    def init_params(self, key: jax.Array):
        return self.family.init_params(self.cfg, key)

    def abstract_params(self):
        return jax.eval_shape(
            lambda k: self.family.init_params(self.cfg, k),
            jax.ShapeDtypeStruct((2,), jnp.uint32))

    def param_count(self) -> int:
        return self.cfg.param_count()

    def active_param_count(self) -> int:
        if hasattr(self.cfg, "active_param_count"):
            return self.cfg.active_param_count()
        return self.param_count()

    def on_mesh(self, mesh) -> "ArchBundle":
        """This bundle with its training forward traced on ``mesh``, so
        that any caller that jits ``make_train_step(bundle.forward)`` runs
        the step sharded by the program's plan (``param_shardings``)."""
        return dataclasses.replace(self, mesh=mesh)

    def param_shardings(self):
        """The parameters' placement on ``mesh``: ``param_specs`` as
        ``NamedSharding``s (the AdamW moments mirror it)."""
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.parallel.sharding import param_specs
        specs = param_specs(self.kind, self.abstract_params(), self.mesh)
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, PartitionSpec))

    # -- steps ---------------------------------------------------------------
    def forward(self, params, batch):
        if self.mesh is None:
            return self._forward(params, batch)
        from repro.runtime import compat
        with compat.use_abstract_mesh(self.mesh.abstract_mesh):
            return self._forward(params, batch)

    def _forward(self, params, batch):
        if self.kind == "audio":
            return self.family.forward(self.cfg, params, batch["tokens"],
                                       batch["frames"])
        if self.kind == "vlm":
            return self.family.forward(self.cfg, params, batch["tokens"],
                                       vision_embeds=batch["vision"])
        return self.family.forward(self.cfg, params, batch["tokens"])

    def init_cache(self, batch: int, max_len: int, kv_dtype=None):
        return self.family.init_cache(self.cfg, batch, max_len,
                                      kv_dtype=kv_dtype)

    def prefill(self, params, tokens, cache, batch_extras=None,
                true_lengths=None):
        if true_lengths is not None and not self.prefill_supports_true_lengths:
            raise ValueError(
                f"{self.arch_id}: family does not support bucketed "
                "(true_lengths) prefill")
        if self.kind == "audio":
            return self.family.prefill(self.cfg, params, tokens, cache,
                                       (batch_extras or {})["frames"])
        if self.kind == "vlm":
            kw = {}
            if true_lengths is not None:
                # the vision prefix is prepended inside prefill, so true
                # sequence lengths shift by the (fixed) prefix size
                vis = (batch_extras or {}).get("vision")
                off = vis.shape[1] if vis is not None else 0
                kw["true_lengths"] = true_lengths + off
            return self.family.prefill(
                self.cfg, params, tokens, cache,
                vision_embeds=(batch_extras or {}).get("vision"), **kw)
        kw = {} if true_lengths is None else {"true_lengths": true_lengths}
        return self.family.prefill(self.cfg, params, tokens, cache, **kw)

    def decode_step(self, params, tokens, cache):
        return self.family.decode_step(self.cfg, params, tokens, cache)

    # -- serving capabilities ---------------------------------------------
    @property
    def prefill_supports_true_lengths(self) -> bool:
        """Whether prefill accepts length-bucketed padded prompts (KV
        caches with per-position writes; SSM states do not qualify)."""
        return bool(getattr(self.family, "PREFILL_TRUE_LENGTHS", False)) \
            and self.kind != "audio"

    @property
    def supports_paged_kv(self) -> bool:
        # vlm excluded: the vision prefix enters through dense prefill's
        # embedding concat; the paged chunked-prefill path is token-only.
        return bool(getattr(self.family, "SUPPORTS_PAGED_KV", False)) \
            and self.kind != "vlm"

    def init_paged_pool(self, num_pages: int, page_size: int, kv_dtype=None):
        return self.family.init_paged_pool(self.cfg, num_pages, page_size,
                                           kv_dtype=kv_dtype)

    def paged_step(self, params, tokens, pool, page_table, lengths, counts):
        return self.family.paged_step(self.cfg, params, tokens, pool,
                                      page_table, lengths, counts)

    def cache_batch_axes(self, cache) -> dict:
        """Batch-axis index for every cache entry (pooled slot writes).
        Families declare ``CACHE_BATCH_AXES``; unknown keys fall back to
        the historical heuristic (axis 0 for 1-D entries, else axis 1)."""
        declared = getattr(self.family, "CACHE_BATCH_AXES", {})
        return {k: declared.get(k, 0 if jnp.ndim(v) == 1 else 1)
                for k, v in cache.items()}

    def min_hbm_bytes(self, shape_name: str) -> int:
        """Theoretical HBM traffic floor for one step of this shape.

        train:   params read fwd+bwd + grads w+r + Adam mu/nu r+w (f32) +
                 layer-boundary activations w+r (bf16)
        decode:  full params read once + KV cache read (+ small writes)
        prefill: params read + activations written + cache written
        """
        sh = SHAPES[shape_name]
        S, B = sh["seq_len"], sh["global_batch"]
        kind = sh["kind"]
        n = self.param_count()
        n_active = self.active_param_count()
        D = self.cfg.d_model
        L = getattr(self.cfg, "n_layers", 1)
        if kind == "train":
            act = 2 * 2 * L * B * S * D           # save+read, bf16
            return int(3 * 2 * n + (4 + 16) * n + 2 * n_active * 0 + act)
        # serving floors
        cache = jax.eval_shape(functools.partial(self.init_cache, B, S))
        cache_bytes = sum(
            int(np.prod(l.shape)) * l.dtype.itemsize
            for l in jax.tree.leaves(cache))
        if kind == "decode":
            return int(2 * n + cache_bytes)
        act = 2 * B * S * D * L
        return int(2 * n + cache_bytes + act)

    # -- shapes ----------------------------------------------------------------
    def supports(self, shape_name: str) -> tuple[bool, str]:
        if shape_name == "long_500k" and not self.sub_quadratic:
            return False, ("full quadratic attention: 512k decode cache "
                           "infeasible; run on SSM/hybrid archs only "
                           "(see DESIGN.md §Arch-applicability)")
        return True, ""

    def step_kind(self, shape_name: str) -> str:
        return SHAPES[shape_name]["kind"]

    def input_specs(self, shape_name: str) -> dict:
        """ShapeDtypeStruct stand-ins for every input of the step function."""
        sh = SHAPES[shape_name]
        S, B = sh["seq_len"], sh["global_batch"]
        kind = sh["kind"]
        i32 = jnp.int32
        D = self.cfg.d_model

        if kind == "train":
            if self.kind == "vlm":
                P = self.cfg.vision_tokens
                return {
                    "tokens": jax.ShapeDtypeStruct((B, S - P), i32),
                    "labels": jax.ShapeDtypeStruct((B, S - P), i32),
                    "vision": jax.ShapeDtypeStruct((B, P, D), self.cfg.dtype),
                }
            if self.kind == "audio":
                return {
                    "tokens": jax.ShapeDtypeStruct((B, S), i32),
                    "labels": jax.ShapeDtypeStruct((B, S), i32),
                    "frames": jax.ShapeDtypeStruct(
                        (B, self.cfg.n_audio_ctx, D), self.cfg.dtype),
                }
            return {
                "tokens": jax.ShapeDtypeStruct((B, S), i32),
                "labels": jax.ShapeDtypeStruct((B, S), i32),
            }

        if kind == "prefill":
            specs = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
            if self.kind == "vlm":
                P = self.cfg.vision_tokens
                specs["tokens"] = jax.ShapeDtypeStruct((B, S - P), i32)
                specs["vision"] = jax.ShapeDtypeStruct((B, P, D),
                                                       self.cfg.dtype)
            if self.kind == "audio":
                specs["frames"] = jax.ShapeDtypeStruct(
                    (B, self.cfg.n_audio_ctx, D), self.cfg.dtype)
            specs["cache"] = jax.eval_shape(
                functools.partial(self.init_cache, B, S))
            return specs

        # decode: one token against a cache of S
        kv_dt = self.kv_dtype_decode if shape_name == "decode_32k" else None
        specs = {
            "tokens": jax.ShapeDtypeStruct((B, 1), i32),
            "cache": jax.eval_shape(
                functools.partial(self.init_cache, B, S, kv_dtype=kv_dt)),
        }
        return specs
