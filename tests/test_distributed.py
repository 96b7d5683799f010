"""Distributed pieces on 8 virtual devices.

These spawn subprocesses because the device count must be fixed BEFORE jax
initializes (and the rest of the suite runs on 1 device per instructions).
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8, timeout: int = 560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert p.returncode == 0, p.stdout + "\n" + p.stderr
    return p.stdout


def test_ring_matmul_and_baseline():
    """Forward vs the dense oracle, and the custom-VJP backward (dA
    output-stationary, dB circulating) vs the oracle's grads."""
    _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.runtime import compat
from repro.parallel.ring_matmul import ring_matmul, ring_matmul_ref, allgather_matmul
mesh = compat.make_mesh((2, 4), ("data", "model"))
a = jax.random.normal(jax.random.PRNGKey(0), (16, 32), jnp.float32)
b = jax.random.normal(jax.random.PRNGKey(1), (32, 24), jnp.float32)
with compat.set_mesh(mesh):
    out = ring_matmul(a, b, mesh, axis="model")
    out2 = allgather_matmul(a, b, mesh, axis="model")
ref = ring_matmul_ref(a, b)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)
np.testing.assert_allclose(np.asarray(out2), np.asarray(ref), rtol=1e-4, atol=1e-4)
def loss(a, b):
    return (ring_matmul(a, b, mesh, axis="model").astype(jnp.float32) ** 2).sum()
def loss_ref(a, b):
    return (ring_matmul_ref(a, b).astype(jnp.float32) ** 2).sum()
with compat.set_mesh(mesh):
    da, db = jax.jit(jax.grad(loss, argnums=(0, 1)))(a, b)
da_r, db_r = jax.grad(loss_ref, argnums=(0, 1))(a, b)
np.testing.assert_allclose(np.asarray(da), np.asarray(da_r), rtol=1e-4, atol=1e-4)
np.testing.assert_allclose(np.asarray(db), np.asarray(db_r), rtol=1e-4, atol=1e-4)
print("ring matmul fwd+grads ok")
""")


def test_ring_matmul_fewer_resident_bytes():
    """The paper's claim at chip scale: ring exchange never duplicates the
    full B operand in memory; the all-gather baseline does."""
    _run("""
import jax, jax.numpy as jnp
from repro.runtime import compat
from repro.parallel.ring_matmul import ring_matmul, allgather_matmul
mesh = compat.make_mesh((1, 8), ("data", "model"))
a = jax.ShapeDtypeStruct((256, 512), jnp.float32)
b = jax.ShapeDtypeStruct((512, 1024), jnp.float32)
with compat.set_mesh(mesh):
    ring = jax.jit(lambda a, b: ring_matmul(a, b, mesh, axis="model")).lower(a, b).compile()
    ag = jax.jit(lambda a, b: allgather_matmul(a, b, mesh, axis="model")).lower(a, b).compile()
rt = ring.memory_analysis().temp_size_in_bytes
at = ag.memory_analysis().temp_size_in_bytes
assert rt < at, (rt, at)
print("ring temp", rt, "< allgather temp", at)
""")


def test_pipeline_parallel_forward():
    _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.runtime import compat
from repro.parallel.pipeline import pipeline_forward
mesh = compat.make_mesh((2, 2, 2), ("pod", "data", "model"))
def stage_fn(params, x):
    return jnp.tanh(x @ params["w"])
sp = {"w": jax.random.normal(jax.random.PRNGKey(2), (2, 8, 8), jnp.float32) * 0.5}
xm = jax.random.normal(jax.random.PRNGKey(3), (4, 3, 8), jnp.float32)
with compat.set_mesh(mesh):
    out = jax.jit(lambda p, x: pipeline_forward(stage_fn, p, x, mesh))(sp, xm)
ref = xm
for s in range(2):
    ref = jnp.tanh(ref @ sp["w"][s])
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)
""")


def test_moe_distribution_modes():
    _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.runtime import compat
from repro.models.layers import MoEConfig, _moe_local, moe_layer
key = jax.random.PRNGKey(0); D = 12
mesh = compat.make_mesh((2, 4), ("data", "model"))
for E, S in [(8, 8), (8, 1), (6, 8), (6, 1)]:
    cfg = MoEConfig(n_experts=E, top_k=2, d_ff=16, capacity_factor=8.0)
    p = {
        "router": jax.random.normal(key, (D, E), jnp.float32) * 0.5,
        "w_gate": jax.random.normal(jax.random.fold_in(key,1), (E, D, 16), jnp.float32) * 0.3,
        "w_up": jax.random.normal(jax.random.fold_in(key,2), (E, D, 16), jnp.float32) * 0.3,
        "w_down": jax.random.normal(jax.random.fold_in(key,3), (E, 16, D), jnp.float32) * 0.3,
    }
    x = jax.random.normal(jax.random.fold_in(key,4), (4, S, D), jnp.float32)
    ref, _ = _moe_local(x, p, cfg)
    with compat.set_mesh(mesh):
        out, _ = jax.jit(lambda x, p: moe_layer(x, p, cfg))(x, p)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)
print("all moe modes ok")
""")


def test_sharded_train_step_matches_single_device():
    """The jit'd train step under a (2,4) mesh produces the same loss as the
    unsharded step — distribution must not change the math."""
    _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.runtime import compat
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_bundle
from repro.optim import adamw_init
from repro.parallel.sharding import param_specs
from repro.training import TrainHyper, make_train_step
bundle = get_bundle("qwen3-4b", smoke=True)
params = bundle.init_params(jax.random.PRNGKey(0))
opt = adamw_init(params)
k = jax.random.PRNGKey(1)
batch = {"tokens": jax.random.randint(k, (8, 16), 0, bundle.cfg.vocab),
         "labels": jax.random.randint(k, (8, 16), 0, bundle.cfg.vocab)}
step = make_train_step(bundle.forward, TrainHyper())
_, _, m_ref = jax.jit(step)(params, opt, batch)

mesh = compat.make_mesh((2, 4), ("data", "model"))
pspecs = param_specs(bundle.kind, params, mesh)
psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                   is_leaf=lambda x: isinstance(x, P))
with compat.set_mesh(mesh):
    params_s = jax.device_put(params, psh)
    opt_s = adamw_init(params_s)
    _, _, m_sh = jax.jit(step)(params_s, opt_s, batch)
assert abs(float(m_ref["ce"]) - float(m_sh["ce"])) < 2e-2, (float(m_ref["ce"]), float(m_sh["ce"]))
print("sharded ce", float(m_sh["ce"]), "ref", float(m_ref["ce"]))
""", timeout=560)


def test_compressed_gradient_psum():
    _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.runtime import compat
from jax.sharding import PartitionSpec as P
from repro.optim.compression import ef_compressed_psum, init_error_feedback
mesh = compat.make_mesh((2, 2, 2), ("pod", "data", "model"))
g = {"w": jax.random.normal(jax.random.PRNGKey(4), (8, 8), jnp.float32)}
e = init_error_feedback(g)
fn = compat.shard_map(lambda g, e: ef_compressed_psum(g, e, "pod"),
                   mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()))
with compat.set_mesh(mesh):
    rg, re = jax.jit(fn)(g, e)
err = np.abs(np.asarray(rg["w"]) - np.asarray(g["w"])).max()
amax = np.abs(np.asarray(g["w"])).max()
assert err <= amax / 127 + 1e-6
# error feedback: the residual equals what quantization dropped
np.testing.assert_allclose(np.asarray(re["w"]),
                           np.asarray(g["w"] - rg["w"]), rtol=1e-5, atol=1e-6)
""")


def test_ring_attention_matches_reference():
    """Both context-parallel modes ('replicated' B5 and 'ring' B6, selected
    via the policy argument — no module-global monkeypatching) match the
    full oracle; the replicated path's shard_map-AD grads still match."""
    _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.runtime import compat
from repro.models.layers import _attention_ring, _grouped_scores_full
mesh = compat.make_mesh((2, 4), ("data", "model"))
key = jax.random.PRNGKey(0)
B, S, H, Dh = 4, 32, 8, 16
q = jax.random.normal(key, (B, S, H, Dh), jnp.float32)
k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, 2, Dh), jnp.float32)
v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, 2, Dh), jnp.float32)
ref = _grouped_scores_full(q, k, v, causal=True, window=None)
for mode in ("replicated", "ring"):
    with compat.set_mesh(mesh):
        out = jax.jit(lambda q, k, v: _attention_ring(q, k, v, causal=True, window=None, ring=mode))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-4, atol=3e-4)
def loss(q, k, v):
    return (_attention_ring(q, k, v, causal=True, window=None, ring="replicated") ** 2).sum()
def loss_ref(q, k, v):
    return (_grouped_scores_full(q, k, v, causal=True, window=None) ** 2).sum()
with compat.set_mesh(mesh):
    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
for a, b in zip(g, g_ref):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4)
print("ring attention ok")
""")


def test_ring_vjp_grads_match_dense():
    """The memory-flat ring custom VJP: dq/dk/dv vs dense XLA attention
    grads for causal, sliding-window, GQA and non-causal cases (fp32
    tolerance on the 8-device host mesh) — the §Perf B6 acceptance."""
    _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.runtime import compat
from repro.parallel.ring_attention import ring_attention
from repro.models.layers import _grouped_scores_full
mesh = compat.make_mesh((2, 4), ("data", "model"))
key = jax.random.PRNGKey(0)
cases = [
    (4, 32, 8, 2, 16, True, None),    # GQA (G=4), causal
    (4, 32, 8, 8, 16, True, 8),       # MHA, sliding window
    (2, 64, 4, 2, 8, True, 12),       # GQA + window
    (2, 64, 4, 4, 8, False, None),    # non-causal, unmasked
]
for B, S, H, Hkv, Dh, causal, window in cases:
    q = jax.random.normal(key, (B, S, H, Dh), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, Dh), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, Hkv, Dh), jnp.float32)
    ref = _grouped_scores_full(q, k, v, causal=causal, window=window)
    def loss(q, k, v):
        return (ring_attention(q, k, v, causal=causal, window=window).astype(jnp.float32) ** 2).sum()
    def loss_ref(q, k, v):
        return (_grouped_scores_full(q, k, v, causal=causal, window=window).astype(jnp.float32) ** 2).sum()
    with compat.set_mesh(mesh):
        out = jax.jit(lambda q, k, v: ring_attention(q, k, v, causal=causal, window=window))(q, k, v)
        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-4, atol=3e-4)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4,
            err_msg=f"d{nm} causal={causal} window={window} Hkv={Hkv}")
    print("ok", B, S, H, Hkv, causal, window)
print("ring vjp grads match dense")
""")


def test_ring_vjp_saves_no_score_tiles():
    """Saved-residual-size assertion: the naive differentiated ring keeps
    the stacked per-hop score tiles (an f32[m, B/d, Hkv, G, S/m, S/m]
    buffer in its backward HLO); the custom-VJP backward must have no f32
    buffer that large, and a smaller XLA temp arena."""
    _run("""
import re
import jax, jax.numpy as jnp
from repro.runtime import compat
from repro.parallel.ring_attention import ring_attention
mesh = compat.make_mesh((2, 4), ("data", "model"))
B, S, H, Hkv, Dh = 4, 64, 4, 2, 8      # B_l=2, S_l=16, G=2, m=4
key = jax.random.PRNGKey(0)
q = jax.random.normal(key, (B, S, H, Dh), jnp.float32)
k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, Dh), jnp.float32)
v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, Hkv, Dh), jnp.float32)
m, B_l, S_l, G = 4, 2, 16, 2
stack_elems = m * B_l * Hkv * G * S_l * S_l

def max_f32_elems(txt):
    best = 0
    for mt in re.finditer(r"f32\\[([\\d,]+)\\]", txt):
        n = 1
        for d in mt.group(1).split(","):
            n *= int(d)
        best = max(best, n)
    return best

stats = {}
for impl in ("naive", "vjp"):
    def loss(q, k, v):
        return (ring_attention(q, k, v, causal=True, window=None, impl=impl).astype(jnp.float32) ** 2).sum()
    with compat.set_mesh(mesh):
        comp = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(q, k, v).compile()
    stats[impl] = (max_f32_elems(comp.as_text()),
                   compat.memory_stats(comp)["temp_bytes"])
# detector sanity: the naive backward DOES stack one tile per hop...
assert stats["naive"][0] >= stack_elems, stats
# ...and the custom VJP retains no buffer anywhere near the stack
assert stats["vjp"][0] < stack_elems, stats
assert stats["vjp"][1] < stats["naive"][1], stats
print("no score tiles saved:", stats)
""")


def test_ring_is_default_long_seq_path():
    """Policy wiring: with the default 'auto' policy, attention() routes
    long sequences through the ppermute ring (the jaxpr carries ppermute
    collectives); REPRO_RING_ATTN=off routes back to the constraint
    path.  The threshold env shrinks 'long' to test-sized sequences."""
    _run("""
import os
import jax, jax.numpy as jnp
from repro.runtime import compat
from repro.models.layers import attention
mesh = compat.make_mesh((2, 4), ("data", "model"))
key = jax.random.PRNGKey(0)
q = jax.random.normal(key, (4, 64, 8, 16), jnp.float32)
k = jax.random.normal(key, (4, 64, 2, 16), jnp.float32)
v = jax.random.normal(key, (4, 64, 2, 16), jnp.float32)
os.environ["REPRO_RING_ATTN_THRESHOLD"] = "64"
def jaxpr(q, k, v):
    with compat.set_mesh(mesh):
        return str(jax.make_jaxpr(
            lambda q, k, v: attention(q, k, v, causal=True, full_threshold=32))(q, k, v))
assert "ppermute" in jaxpr(q, k, v)            # default auto -> ring
os.environ["REPRO_RING_ATTN_THRESHOLD"] = "128"
assert "ppermute" not in jaxpr(q, k, v)        # below threshold -> replicated
os.environ["REPRO_RING_ATTN"] = "ring"
assert "ppermute" in jaxpr(q, k, v)            # forced ring beats threshold
os.environ["REPRO_RING_ATTN"] = "off"
assert "ppermute" not in jaxpr(q, k, v)
print("ring default-path policy ok")
""")


def test_paged_pool_sharded_across_mesh():
    """The serving block pool lives across the mesh: pool pages carry the
    paged_pool_specs sharding and the paged engine still emits the same
    greedy tokens as the single-host dense engine."""
    _run("""
import jax, numpy as np
from repro.runtime import compat
from repro.launch.serve import build_engine

mesh = compat.make_mesh((2, 4), ("data", "model"))
prompts = [np.arange(4 + 3 * i, dtype=np.int32) % 96 for i in range(4)]

dense, _ = build_engine("qwen3-4b", slots=2, max_len=48, max_new=4)
for p in prompts:
    dense.submit(p)
ref = dense.run()

paged, _ = build_engine("qwen3-4b", slots=2, max_len=48, max_new=4,
                        kv_mode="paged", page_size=8, mesh=mesh)
for p in prompts:
    paged.submit(p)
out = paged.run()
shardings = {k: v.sharding for k, v in paged.pool.items()}
assert any(s.is_fully_replicated is False for s in shardings.values()), shardings
assert out == ref, (out, ref)
print("paged pool sharded ok")
""")


def test_ring_fused_pallas_hop_matches_einsum():
    """The fused per-hop fold (Pallas flash kernels inside both ring
    passes, traced axis-index offsets through the scalar-prefetch
    operand) is numerically the einsum fold: outputs and dq/dk/dv grads
    match for causal, windowed and GQA cases on the 8-device host mesh
    (interpret mode — the compiled-Mosaic run is a ROADMAP item)."""
    _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.runtime import compat
from repro.parallel.ring_attention import ring_attention
mesh = compat.make_mesh((2, 4), ("data", "model"))
key = jax.random.PRNGKey(3)
cases = [
    (2, 64, 4, 4, 16, True, None),    # MHA causal
    (2, 64, 8, 2, 16, True, 24),      # GQA + sliding window
    (2, 64, 4, 2, 8, False, None),    # non-causal GQA
]
for B, S, H, Hkv, Dh, causal, window in cases:
    q = jax.random.normal(key, (B, S, H, Dh), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, Dh), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, Hkv, Dh), jnp.float32)
    def loss(q, k, v, fused):
        return (ring_attention(q, k, v, causal=causal, window=window, fused=fused).astype(jnp.float32) ** 2).sum()
    with compat.set_mesh(mesh):
        o_e = jax.jit(lambda q, k, v: ring_attention(q, k, v, causal=causal, window=window, fused=False))(q, k, v)
        o_f = jax.jit(lambda q, k, v: ring_attention(q, k, v, causal=causal, window=window, fused=True))(q, k, v)
        g_e = jax.jit(jax.grad(lambda q, k, v: loss(q, k, v, False), argnums=(0, 1, 2)))(q, k, v)
        g_f = jax.jit(jax.grad(lambda q, k, v: loss(q, k, v, True), argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(np.asarray(o_f), np.asarray(o_e), rtol=3e-4, atol=3e-4)
    for a, b, nm in zip(g_f, g_e, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4,
                                   err_msg=f"d{nm} causal={causal} window={window}")
    print("ok", B, S, H, Hkv, causal, window)
print("fused ring hop matches einsum fold")
""")


def test_on_mesh_train_step_carries_scopes_in_hlo_metadata():
    """A bundle placed ``on_mesh`` of a (1, 4) host mesh, the ring forced
    (1 x 4096 tokens, 1024-token shards): the compiled step's op metadata
    (what a profiler trace shows as ``tf_op``) names the training layer's
    scopes and the ring's ``ring_hop``, in the forward and the backward
    pass."""
    _run("""
import os, re
os.environ["REPRO_RING_ATTN"] = "ring"
import jax, jax.numpy as jnp
from repro.configs import get_bundle
from repro.launch.mesh import make_host_mesh
from repro.optim import adamw_init
from repro.training import TrainHyper, make_train_step
bundle = get_bundle("qwen3-4b", smoke=True).on_mesh(make_host_mesh(4))
params = jax.jit(bundle.init_params,
                 out_shardings=bundle.param_shardings())(jax.random.PRNGKey(0))
opt = jax.jit(adamw_init)(params)
tok = jnp.zeros((1, 4096), jnp.int32)     # over attention's full_threshold
step = jax.jit(make_train_step(bundle.forward, TrainHyper()))
hlo = step.lower(params, opt, {"tokens": tok, "labels": tok}).compile().as_text()
names = re.findall(r'op_name="([^"]*)"', hlo)
words = set()
for n in names:
    for part in n.split("/")[:-1]:
        words.update(re.findall(r"[A-Za-z_][\\w.\\-]*", part))
want = {"qkv", "attention", "mlp", "lm_head", "loss", "adamw", "ring_hop"}
assert want <= words, want - words
hops = [n for n in names if "/ring_hop/" in n]
assert any(n.startswith("jit(") and "transpose(" not in n for n in hops), hops[:4]
assert any("transpose(" in n for n in hops), hops[:4]
print("scopes ok", len(hops))
""", devices=4)
