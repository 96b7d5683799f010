"""Ring attention over the ppermute mesh, with a memory-flat custom VJP.

This is the paper's FIFO data-exchange mesh applied to context-parallel
attention at chip scale (§Perf B6).  Queries stay home (output-stationary,
like the paper's stationary PSums), k/v sequence shards hop neighbour to
neighbour via ``jax.lax.ppermute`` (the FIFO hop), and each device folds
the visiting shard into its local rows' online softmax — no k/v all-gather
ever materializes and only one shard is in flight per step.

Forward (per ``model``-axis device, ring of ``m``):
  q_l: (B, S/m, H, Dh) local rows; k_l/v_l: this device's own sequence
  shard.  ``m`` hops of fold-then-permute.  The custom VJP saves ONLY
  ``(o, logsumexp)`` — O(S/m · H · Dh) per device, independent of ``m`` —
  under the names a layer's remat policy keeps
  (``kernels.attention.name_residuals``), so a rematerialised layer's
  backward does not run the forward ring again to get them back.

Backward (a second ring pass with the same hop schedule):
  each hop RECOMPUTES the visiting shard's score tile from
  ``(q, k_hop, lse)``, folds ``dq`` into a local accumulator, and
  circulates ``dk``/``dv`` accumulators ALONGSIDE the k/v shards — a
  shard's gradient rides the ring with it and arrives home exactly when
  the loop ends, so there is no psum and no saved per-hop activation.
  Peak memory is a constant number of shard-sized buffers (the 4-deep
  FIFO analogue).  The naive alternative — reverse-differentiating the
  fold loop — stacks one (S/m x S/m) f32 score tile per hop per layer
  (measured: memory term 17s -> 38s on qwen2.5 train; that measurement
  is what kept the ring opt-in until this VJP).  ``impl='naive'`` keeps
  that path alive as the benchmark baseline.

Each hop, its fold and its ppermutes, runs under the named scope
``ring_hop`` (:data:`HOP_SCOPE`) in both passes, so a profiler trace
tells the hop's device time apart from the rest of attention.

Masking (causal / sliding-window) and GQA grouping are handled here so
callers (``models/layers.attention``) only pick a policy; the varying-
manual-axes typing inside shard_map goes through ``compat.pcast`` /
``compat.match_vma`` like every other shard_map body in the repo.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.runtime import compat

__all__ = ["ring_attention", "data_axes_spec", "HOP_SCOPE"]

# the named scope over each hop's fold and its ppermutes, in both passes
HOP_SCOPE = "ring_hop"


def data_axes_spec(mesh, batch: int):
    """Sharding spec for a batch dim over the data-ish mesh axes ("pod",
    "data"): the axis tuple when ``batch`` divides their product, else
    None (replicate)."""
    daxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dsz = 1
    for a in daxes:
        dsz *= mesh.shape[a]
    if not daxes or batch % dsz != 0:
        return None
    return daxes if len(daxes) > 1 else daxes[0]


@dataclasses.dataclass(frozen=True)
class _RingSpec:
    """Static description of one ring-attention call (hashable: it rides
    ``custom_vjp``'s nondiff_argnums).  ``fused`` folds each visiting
    shard with the Pallas flash kernels (block_q/block_k tile the local
    shard) instead of the XLA einsum chain."""
    mesh: object
    axis: str
    m: int
    causal: bool
    window: int | None
    dspec: tuple | str | None
    fused: bool = False
    block_q: int = 0
    block_k: int = 0
    interpret: bool = False


def _hop_perm(m: int):
    return [(i, (i + 1) % m) for i in range(m)]


def _fused_blocks(S_l: int, Dh: int,
                  itemsize: int = 2) -> tuple[int, int] | None:
    """Autotuned (block_q, block_k) for operands of ``itemsize`` bytes,
    snapped down to divisors of the local shard, or None when the shard is
    too ragged to tile (-> einsum fold)."""
    from repro.core.pallas_bridge import attention_block_shapes
    bq, bk = attention_block_shapes(S_l, S_l, Dh, itemsize=itemsize)
    while bq > 1 and S_l % bq:
        bq //= 2
    while bk > 1 and S_l % bk:
        bk //= 2
    if bq < 8 or bk < 8:
        return None
    return bq, bk


def _flat_heads(x):
    """(B, S, H, Dh) -> (B*H, S, Dh) — the kernels' head-major layout."""
    B, S, H, Dh = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, Dh)


def _unflat_heads(x, B):
    BH, S, Dh = x.shape
    return x.reshape(B, BH // B, S, Dh).transpose(0, 2, 1, 3)


def _masked_scores(qg, kb, *, scale, q_off, k_off, causal, window):
    """(B, Hkv, G, Sq, Sk) f32 score tile of local q rows against ONE
    visiting shard, with the causal/sliding-window band mask applied in
    GLOBAL positions (q_off/k_off may be traced axis-index offsets)."""
    S_q, S_k = qg.shape[1], kb.shape[1]
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kb,
                   preferred_element_type=jnp.float32) * scale
    if not causal and window is None:
        return s
    qpos = q_off + jnp.arange(S_q)[:, None]
    kpos = k_off + jnp.arange(S_k)[None, :]
    mask = jnp.ones((S_q, S_k), bool)
    if causal:
        mask = mask & (qpos >= kpos)
    if window is not None:
        mask = mask & ((qpos - kpos) < window)
    return jnp.where(mask, s, -1e30)


# ---------------------------------------------------------------------------
# per-shard bodies
# ---------------------------------------------------------------------------

def _fused_fwd_body(spec: _RingSpec, q_l, k_l, v_l):
    """Fold-then-permute forward where each hop's fold IS the Pallas flash
    kernel: the hop computes the visiting shard's (o_hop, lse_hop) with
    the band mask shifted to global positions (traced axis-index offsets
    ride the kernel's scalar-prefetch operand), and the per-hop partials
    combine by logsumexp algebra — the same PSum-stationary schedule, with
    the score tile now inside the MXU kernel instead of an XLA einsum."""
    from repro.kernels.attention import flash_attention_fwd_pallas
    # see _fwd_body: partition-id only when a band mask data-depends on it
    needs_pos = spec.causal or spec.window is not None
    idx = jax.lax.axis_index(spec.axis) if needs_pos else 0
    B, S_l, H, Dh = q_l.shape
    Hkv = k_l.shape[2]
    G = H // Hkv
    qf = _flat_heads(q_l)
    q_off = idx * S_l
    perm = _hop_perm(spec.m)

    @jax.named_scope(HOP_SCOPE)
    def step(t, carry):
        k_c, v_c, o_acc, lse = carry
        owner = (idx - t) % spec.m if needs_pos else 0
        o_h, lse_h = flash_attention_fwd_pallas(
            qf, _flat_heads(k_c), _flat_heads(v_c), causal=spec.causal,
            window=spec.window, block_q=spec.block_q, block_k=spec.block_k,
            q_offset=q_off, k_offset=owner * S_l,
            prune=False, interpret=spec.interpret)
        o_h = compat.match_vma(o_h.astype(jnp.float32), qf)
        lse_h = compat.match_vma(lse_h, qf)
        lse_new = jnp.logaddexp(lse, lse_h)
        o_acc = (o_acc * jnp.exp(lse - lse_new)[..., None]
                 + o_h * jnp.exp(lse_h - lse_new)[..., None])
        k_c = jax.lax.ppermute(k_c, spec.axis, perm)
        v_c = jax.lax.ppermute(v_c, spec.axis, perm)
        return (k_c, v_c, o_acc, lse_new)

    vary = lambda x: compat.match_vma(x, qf)  # noqa: E731
    st0 = (k_l, v_l,
           vary(jnp.zeros((B * H, S_l, Dh), jnp.float32)),
           vary(jnp.full((B * H, S_l), -1e30, jnp.float32)))
    _, _, o_acc, lse = jax.lax.fori_loop(0, spec.m, step, st0)
    o = _unflat_heads(o_acc, B).astype(q_l.dtype)     # (B, S_l, H, Dh)
    return o, lse.reshape(B, Hkv, G, S_l)


def _fused_bwd_body(spec: _RingSpec, q_l, k_l, v_l, o_l, lse_l, do_l):
    """Second ring pass with the Pallas backward kernels doing each hop's
    re-stream: dq folds locally, dk/dv accumulators ride the ring with
    their shards (all f32 until the final cast)."""
    from repro.kernels.attention import flash_attention_bwd_pallas
    needs_pos = spec.causal or spec.window is not None
    idx = jax.lax.axis_index(spec.axis) if needs_pos else 0
    B, S_l, H, Dh = q_l.shape
    Hkv = k_l.shape[2]
    f32 = jnp.float32
    qf = _flat_heads(q_l)
    dof = _flat_heads(do_l)
    of = _flat_heads(o_l)
    lsef = lse_l.reshape(B, H, S_l).reshape(B * H, S_l)
    delta = jnp.sum(of.astype(f32) * dof.astype(f32), axis=-1)
    q_off = idx * S_l
    perm = _hop_perm(spec.m)

    @jax.named_scope(HOP_SCOPE)
    def step(t, carry):
        k_c, v_c, dk_c, dv_c, dq = carry
        owner = (idx - t) % spec.m if needs_pos else 0
        dq_h, dk_h, dv_h = flash_attention_bwd_pallas(
            qf, _flat_heads(k_c), _flat_heads(v_c), dof, lsef, delta,
            causal=spec.causal, window=spec.window, block_q=spec.block_q,
            block_k=spec.block_k, q_offset=q_off,
            k_offset=owner * S_l, prune=False,
            interpret=spec.interpret)
        dq = dq + compat.match_vma(dq_h, qf)
        dk_c = dk_c + _unflat_heads(compat.match_vma(dk_h, qf), B)
        dv_c = dv_c + _unflat_heads(compat.match_vma(dv_h, qf), B)
        k_c = jax.lax.ppermute(k_c, spec.axis, perm)
        v_c = jax.lax.ppermute(v_c, spec.axis, perm)
        dk_c = jax.lax.ppermute(dk_c, spec.axis, perm)
        dv_c = jax.lax.ppermute(dv_c, spec.axis, perm)
        return (k_c, v_c, dk_c, dv_c, dq)

    vary = lambda x: compat.match_vma(x, qf)  # noqa: E731
    st0 = (k_l, v_l,
           vary(jnp.zeros((B, S_l, Hkv, Dh), f32)),
           vary(jnp.zeros((B, S_l, Hkv, Dh), f32)),
           vary(jnp.zeros((B * H, S_l, Dh), f32)))
    _, _, dk, dv, dq = jax.lax.fori_loop(0, spec.m, step, st0)
    dq = _unflat_heads(dq, B).astype(q_l.dtype)       # (B, S_l, H, Dh)
    return dq, dk.astype(k_l.dtype), dv.astype(v_l.dtype)


def _fwd_body(spec: _RingSpec, q_l, k_l, v_l):
    """Fold-then-permute forward.  Returns (o, lse); lse is f32
    (B, Hkv, G, S/m) — the only extra residual the VJP keeps."""
    if spec.fused:
        return _fused_fwd_body(spec, q_l, k_l, v_l)
    # axis_index only when a band mask exists: with no mask nothing data-
    # depends on it, and XLA's SPMD partitioner rejects a partition-id it
    # cannot infer as manually sharded.
    needs_pos = spec.causal or spec.window is not None
    idx = jax.lax.axis_index(spec.axis) if needs_pos else 0
    B, S_l, H, Dh = q_l.shape
    Hkv = k_l.shape[2]
    G = H // Hkv
    qg = q_l.reshape(B, S_l, Hkv, G, Dh)
    scale = 1.0 / math.sqrt(Dh)
    q_off = idx * S_l
    perm = _hop_perm(spec.m)

    @jax.named_scope(HOP_SCOPE)
    def step(t, carry):
        k_c, v_c, mx, l, acc = carry
        owner = (idx - t) % spec.m
        s = _masked_scores(qg, k_c, scale=scale, q_off=q_off,
                           k_off=owner * S_l, causal=spec.causal,
                           window=spec.window)
        m_new = jnp.maximum(mx, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(mx - m_new)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p.astype(v_c.dtype), v_c,
            preferred_element_type=jnp.float32)
        # hand the shard to the neighbour — the FIFO hop
        k_c = jax.lax.ppermute(k_c, spec.axis, perm)
        v_c = jax.lax.ppermute(v_c, spec.axis, perm)
        return (k_c, v_c, m_new, l, acc)

    vary = lambda x: compat.match_vma(x, qg)  # noqa: E731
    st0 = (k_l, v_l,
           vary(jnp.full((B, Hkv, G, S_l), -1e30, jnp.float32)),
           vary(jnp.zeros((B, Hkv, G, S_l), jnp.float32)),
           vary(jnp.zeros((B, Hkv, G, S_l, Dh), jnp.float32)))
    _, _, mx, l, acc = jax.lax.fori_loop(0, spec.m, step, st0)
    l_safe = jnp.where(l == 0, 1.0, l)
    o = (acc / l_safe[..., None]).transpose(0, 3, 1, 2, 4) \
        .reshape(B, S_l, H, Dh).astype(q_l.dtype)
    lse = mx + jnp.log(l_safe)
    return o, lse


def _naive_body(spec: _RingSpec, q_l, k_l, v_l):
    """The pre-VJP path: same forward, but its backward is whatever
    reverse-differentiating the fold loop produces (one stacked score
    tile per hop).  Kept as the §Perf B6 benchmark baseline."""
    o, _ = _fwd_body(spec, q_l, k_l, v_l)
    return o


def _bwd_body(spec: _RingSpec, q_l, k_l, v_l, o_l, lse_l, do_l):
    """Second ring pass: recompute each visiting shard's tile, fold dq
    locally, circulate dk/dv with the shards.  After m hops the
    accumulators are home — no psum."""
    if spec.fused:
        return _fused_bwd_body(spec, q_l, k_l, v_l, o_l, lse_l, do_l)
    needs_pos = spec.causal or spec.window is not None
    idx = jax.lax.axis_index(spec.axis) if needs_pos else 0
    B, S_l, H, Dh = q_l.shape
    Hkv = k_l.shape[2]
    G = H // Hkv
    f32 = jnp.float32
    qg = q_l.reshape(B, S_l, Hkv, G, Dh).astype(f32)
    dog = do_l.reshape(B, S_l, Hkv, G, Dh).astype(f32)
    og = o_l.reshape(B, S_l, Hkv, G, Dh).astype(f32)
    # di = rowsum(do * o), shared by the dq and dk products (flash bwd)
    delta = jnp.einsum("bqkgd,bqkgd->bkgq", dog, og)
    scale = 1.0 / math.sqrt(Dh)
    q_off = idx * S_l
    perm = _hop_perm(spec.m)

    @jax.named_scope(HOP_SCOPE)
    def step(t, carry):
        k_c, v_c, dk_c, dv_c, dq = carry
        owner = (idx - t) % spec.m
        s = _masked_scores(qg, k_c, scale=scale, q_off=q_off,
                           k_off=owner * S_l, causal=spec.causal,
                           window=spec.window)
        p = jnp.exp(s - lse_l[..., None])        # masked entries -> exp(-inf)=0
        dv_c = dv_c + jnp.einsum("bkgqs,bqkgd->bskd", p, dog)
        dp = jnp.einsum("bqkgd,bskd->bkgqs", dog, v_c,
                        preferred_element_type=f32)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bkgqs,bskd->bqkgd", ds, k_c,
                             preferred_element_type=f32)
        dk_c = dk_c + jnp.einsum("bkgqs,bqkgd->bskd", ds, qg)
        # shard AND its gradient accumulator ride the ring together
        k_c = jax.lax.ppermute(k_c, spec.axis, perm)
        v_c = jax.lax.ppermute(v_c, spec.axis, perm)
        dk_c = jax.lax.ppermute(dk_c, spec.axis, perm)
        dv_c = jax.lax.ppermute(dv_c, spec.axis, perm)
        return (k_c, v_c, dk_c, dv_c, dq)

    vary = lambda x: compat.match_vma(x, qg)  # noqa: E731
    st0 = (k_l, v_l,
           vary(jnp.zeros((B, S_l, Hkv, Dh), f32)),
           vary(jnp.zeros((B, S_l, Hkv, Dh), f32)),
           vary(jnp.zeros((B, S_l, Hkv, G, Dh), f32)))
    _, _, dk, dv, dq = jax.lax.fori_loop(0, spec.m, step, st0)
    dq = dq.reshape(B, S_l, H, Dh).astype(q_l.dtype)
    return dq, dk.astype(k_l.dtype), dv.astype(v_l.dtype)


# ---------------------------------------------------------------------------
# custom VJP plumbing
# ---------------------------------------------------------------------------

def _qkv_spec(spec: _RingSpec):
    return P(spec.dspec, spec.axis, None, None)


def _shard(spec: _RingSpec, body, in_specs, out_specs):
    # JAX 0.9.0 limitation: the Pallas HLO interpreter (the fused fold
    # off-TPU; jax/_src/pallas/hlo_interpreter.py, pallas_call_hlo_interpret)
    # evaluates the kernel body with varying blocks but unvarying grid
    # indices, and `dynamic_slice`'s vma rule raises "Primitive
    # dynamic_slice requires varying manual axes to match" (the error's
    # own advice: report upstream, pass check_vma=False).  Drop this once
    # an upgraded interpreter passes test_ring_fused_pallas_hop_matches_
    # einsum with the check on.  Compiled Mosaic kernels keep the check
    # (their out-shapes carry the vma), guarded by
    # test_ring_fused_hop_compiles_on_2x2.
    kw = {"check_vma": False} if spec.fused and spec.interpret else {}
    return compat.shard_map(functools.partial(body, spec), mesh=spec.mesh,
                            in_specs=in_specs, out_specs=out_specs, **kw)


def _shard_fwd(spec: _RingSpec, q, k, v):
    qs = _qkv_spec(spec)
    fn = _shard(spec, _fwd_body, (qs, qs, qs),
                (qs, P(spec.dspec, None, None, spec.axis)))
    return fn(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ring_attn(spec: _RingSpec, q, k, v):
    o, _ = _shard_fwd(spec, q, k, v)
    return o


def _ring_attn_fwd(spec: _RingSpec, q, k, v):
    from repro.kernels.attention import name_residuals
    o, lse = name_residuals(*_shard_fwd(spec, q, k, v))
    return o, (q, k, v, o, lse)


def _ring_attn_bwd(spec: _RingSpec, res, do):
    q, k, v, o, lse = res
    qs = _qkv_spec(spec)
    fn = _shard(spec, _bwd_body,
                (qs, qs, qs, qs, P(spec.dspec, None, None, spec.axis), qs),
                (qs, qs, qs))
    return fn(q, k, v, o, lse, do)


_ring_attn.defvjp(_ring_attn_fwd, _ring_attn_bwd)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def _decide_fused(fused: bool | None, S_global: int, S_local: int, Dh: int,
                  itemsize: int = 2):
    """Resolve the per-hop fold engine: explicit ``fused`` wins, else the
    flash policy (REPRO_FLASH_ATTN / backend) judged on the GLOBAL
    sequence (the ring still folds all of it, one shard per hop).
    Returns (fused, block_q, block_k, interpret); fused falls off when
    the local shard won't tile."""
    interpret = jax.default_backend() != "tpu"
    if fused is None:
        from repro.configs import base as cbase
        fused = cbase.decide_flash(cbase.flash_attn_policy(None),
                                   seq_len=S_global, kv_len=S_global,
                                   on_tpu=not interpret) == "pallas"
    if not fused:
        return False, 0, 0, interpret
    blocks = _fused_blocks(S_local, Dh, itemsize)
    if blocks is None:
        return False, 0, 0, interpret
    return True, blocks[0], blocks[1], interpret


def ring_attention(q, k, v, *, causal=True, window=None, mesh=None,
                   axis: str = "model", impl: str = "vjp",
                   fused: bool | None = None):
    """Context-parallel attention on the ppermute ring.

    q: (B, S, H, Dh); k/v: (B, S, Hkv, Dh) with H % Hkv == 0 (GQA).
    Returns the (B, S, H, Dh) output, or None when the ring does not
    apply (no ambient/explicit mesh, axis absent or size 1, S does not
    divide the ring, cross-attention).  ``impl``: "vjp" (memory-flat
    custom VJP, the default) or "naive" (reverse-differentiated fold —
    benchmark baseline only).  ``fused`` selects the Pallas flash kernels
    for the per-hop score-tile fold in BOTH ring passes (None: follow the
    flash policy — on by default on TPU).
    """
    if mesh is None:
        mesh = compat.get_abstract_mesh()
    if mesh is None or getattr(mesh, "empty", False):
        return None
    if axis not in mesh.axis_names or mesh.shape[axis] == 1:
        return None
    try:
        if mesh._are_all_axes_manual:    # already inside a shard_map
            return None
    except AttributeError:
        pass
    m = int(mesh.shape[axis])
    B, S, H, Dh = q.shape
    if S % m != 0 or k.shape[1] != S:
        return None
    use_fused, bq, bk, interp = _decide_fused(fused, S, S // m, Dh,
                                              q.dtype.itemsize)
    spec = _RingSpec(mesh=mesh, axis=axis, m=m, causal=bool(causal),
                     window=None if window is None else int(window),
                     dspec=data_axes_spec(mesh, B), fused=use_fused,
                     block_q=bq, block_k=bk, interpret=interp)
    if impl == "naive":
        qs = _qkv_spec(spec)
        fn = compat.shard_map(
            functools.partial(_naive_body, spec), mesh=spec.mesh,
            in_specs=(qs, qs, qs), out_specs=qs)
        return fn(q, k, v)
    if impl != "vjp":
        raise ValueError(f"ring_attention impl {impl!r} not in "
                         "('vjp', 'naive')")
    from repro.kernels.ops import _record_dispatch
    _record_dispatch("ring_attention", impl="pallas" if use_fused else "xla",
                     ring=m, s=S, block_q=bq, block_k=bk)
    return _ring_attn(spec, q, k, v)
