"""Compile the main-path kernels for a described TPU v5e, with no chip.

Interpret mode (every other kernel test) never checks Mosaic's tiling
rules or VMEM budget; the TPU compiler, which is installed even where no
chip is attached, does.  Each test lowers a kernel (or one serving layer)
at qwen3-4b widths for one device of a ``v5e:2x2`` topology, or for all
four (the ring), and asserts the compiled module holds the Mosaic kernel
(``tpu_custom_call``).

The topology is described inside a module-scoped fixture: only one
process at a time may load the TPU library, so nothing here touches it
while the module is imported.
"""
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.pallas_bridge import attention_block_shapes
from repro.kernels import attention as katt
from repro.kernels import paged_attention as kpaged

# qwen3-4b attention widths
H, HKV, DH = 32, 8, 128
G = H // HKV


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import compilation_cache, topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _flash_spec(S: int) -> katt.FlashSpec:
    bq, bk = attention_block_shapes(S, S, DH)
    return katt.FlashSpec(causal=True, window=None, block_q=bq, block_k=bk,
                          scale=1.0 / math.sqrt(DH), kv_len=S, q_len=S,
                          prune=True, interpret=False)


@pytest.mark.parametrize("S", [2048, 32768])
def test_flash_forward_compiles(one_chip, S):
    spec = _flash_spec(S)
    q = _struct((H, S, DH), jnp.bfloat16, one_chip)
    kv = _struct((HKV, S, DH), jnp.bfloat16, one_chip)
    _assert_mosaic(lambda q, k, v: katt.flash_attention_train(spec, q, k, v),
                   q, kv, kv)


def test_flash_forward_backward_compiles(one_chip):
    spec = _flash_spec(2048)
    q = _struct((H, 2048, DH), jnp.bfloat16, one_chip)
    kv = _struct((HKV, 2048, DH), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        o = katt.flash_attention_train(spec, q, k, v)
        return jnp.sum(o.astype(jnp.float32))

    _assert_mosaic(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_paged_decode_compiles(one_chip, kv_dtype):
    """The pool as the serving step stores it, (P, page, Hkv, Dh), and
    per-slot (B, MP) tables, at a view of 64 pages and at docqa's 394,
    which is no multiple of the block."""
    slots, page = 8, 16
    for per_slot in (64, 394):
        n_pages = slots * per_slot + 1
        ppb = kpaged.pages_per_block(page, HKV, DH,
                                     jnp.dtype(kv_dtype).itemsize, per_slot)
        assert per_slot == 64 or per_slot % ppb
        q = _struct((slots, H, DH), jnp.bfloat16, one_chip)
        pool = _struct((n_pages, page, HKV, DH), kv_dtype, one_chip)
        table = _struct((slots, per_slot), jnp.int32, one_chip)
        lengths = _struct((slots,), jnp.int32, one_chip)
        args = [q, pool, pool, table, lengths]
        if kv_dtype == jnp.int8:
            scales = _struct((n_pages, page, HKV), jnp.float32, one_chip)
            args += [scales, scales]

        def decode(*a, ppb=ppb):
            return kpaged.paged_flash_decode_pallas(*a, pages_per_block=ppb)

        _assert_mosaic(decode, *args)


def test_qwen3_4b_paged_step_layer_compiles(one_chip, monkeypatch):
    """One full-width qwen3-4b decode layer through ``paged_step``, with
    the paged kernel compiled rather than interpreted."""
    from repro.configs import get_bundle
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    bundle = get_bundle("qwen3-4b", smoke=False)
    cfg = dataclasses.replace(bundle.cfg, n_layers=1, attn_impl="pallas")
    slots, page, per_slot = 8, 16, 64

    def place(tree):
        return jax.tree.map(
            lambda s: _struct(s.shape, s.dtype, one_chip), tree)

    params = place(jax.eval_shape(
        lambda k: bundle.family.init_params(cfg, k),
        jax.ShapeDtypeStruct((2,), jnp.uint32)))
    pool = place(jax.eval_shape(
        lambda: bundle.family.init_paged_pool(
            cfg, slots * per_slot + 1, page)))
    tokens = _struct((slots, 1), jnp.int32, one_chip)
    table = _struct((slots, per_slot), jnp.int32, one_chip)
    lengths = _struct((slots,), jnp.int32, one_chip)

    def step(params, tokens, pool, table, lengths, counts):
        return bundle.family.paged_step(cfg, params, tokens, pool, table,
                                        lengths, counts)

    # the kernel's op is named by its jitted wrapper, by which a trace's
    # device ops (and the benchmark's decode readers) find it
    text = _assert_mosaic(step, params, tokens, pool, table, lengths,
                          lengths)
    assert re.search(r"%paged_flash_decode(\.\d+)? = \S+ custom-call\(",
                     text)
    # the kernel reads the pool in place: no transpose or copy of a
    # whole layer pool, in its stored or in a kv-head-major shape
    n_pages = slots * per_slot + 1
    pools = {f"[{n_pages},{page},{HKV},{DH}]", f"[{HKV},{n_pages},{page},{DH}]"}
    relaid = [m.group(1) for m in re.finditer(
        r"= \w+(\[[\d,]+\])\{[^}]*\} (?:copy|transpose)\(", text)]
    assert not pools & set(relaid), relaid


def test_ring_fused_hop_compiles_on_2x2(topo, monkeypatch):
    """The four-chip long-sequence path: ring attention over the model
    axis of a (1, 4) mesh, each hop folded by the compiled flash kernels
    inside shard_map (fwd + the memory-flat custom-VJP bwd)."""
    _ring_fused_hop_compiles(topo, monkeypatch, jnp.bfloat16, 4096)


def test_ring_fused_hop_compiles_on_2x2_f32(topo, monkeypatch):
    """The ``qwen3-4b-16l.ring-train`` cell's ring: float32, 2048-token
    shards.  At the blocks tuned for bf16 its dk/dv kernel asked Mosaic
    for 17.07 MiB of scoped VMEM, over the 16 MiB limit."""
    _ring_fused_hop_compiles(topo, monkeypatch, jnp.float32, 8192)


def _ring_fused_hop_compiles(topo, monkeypatch, dtype, S):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.obs import REGISTRY
    from repro.parallel.ring_attention import ring_attention
    from repro.runtime import compat
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = compat.make_mesh((1, 4), ("data", "model"), devices=topo.devices)
    seq = NamedSharding(mesh, P(None, "model", None, None))
    q = _struct((1, S, H, DH), dtype, seq)
    kv = _struct((1, S, HKV, DH), dtype, seq)

    def loss(q, k, v):
        o = ring_attention(q, k, v, causal=True, mesh=mesh, fused=True)
        return jnp.sum(o.astype(jnp.float32))

    def fused_hops():
        return REGISTRY.get_counter("kernel_dispatch",
                                    kernel="ring_attention", impl="pallas")

    before = fused_hops()
    with compat.set_mesh(mesh):
        _assert_mosaic(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert fused_hops() > before
