"""The configuration's counts against a hand count, and the float32
reference against the program's own forward pass at smoke size."""
import numpy as np
import pytest

import smoke_root
import harness

CFG = smoke_root.SMOKE_CONFIG


@pytest.fixture(scope="module")
def fam():
    return harness.family_module("qwen3")


def test_flop_and_byte_counts_by_hand(fam):
    # smoke sizes: L=2 D=64 H=4 Kv=2 Dh=16 F=128 V=256
    per_layer = 64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 128
    assert fam.linear_params(CFG) == 2 * per_layer + 64 * 256 == 90112
    assert fam.token_flops(CFG, 3, 10) == 2 * 90112 * 3 + 4 * 2 * 4 * 16 * 10
    flops, nbytes = fam.paged_decode_cost(CFG, rows=2, attended=10)
    assert flops == 4 * 2 * 4 * 16 * 10
    # K and V rows of 10 keys (2 heads x 16 x 2 bytes) per layer, plus q
    # and out of 2 rows (4 heads x 16 x 2 bytes) per layer
    assert nbytes == 2 * (2 * 2 * 16 * 2 * 10) + 2 * (2 * 2 * 4 * 16 * 2)


def test_counts_agree_with_the_program_parameter_tree(fam):
    import jax
    full = harness.load_json(harness.config_path("qwen3-4b"))
    tree = fam.program_bundle(full).abstract_params()
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    s = fam.sizes(full)
    gains = s["L"] * (2 * s["D"] + 2 * s["Dh"]) + s["D"]  # ln1 ln2 qk ln_f
    assert total == fam.linear_params(full) + s["V"] * s["D"] + gains
    assert total == 4_411_424_256          # 8.82 GB in bf16


def test_weights_follow_the_program_layout(fam):
    import jax
    bundle = fam.program_bundle(CFG)
    p = fam.make_params(bundle, harness.jax_key(2**33 + 1))
    want = bundle.abstract_params()
    assert jax.tree.structure(p) == jax.tree.structure(want)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in
               zip(jax.tree.leaves(p), jax.tree.leaves(want)))
    q = fam.make_params(bundle, harness.jax_key(2**33 + 1))
    assert all((np.asarray(a) == np.asarray(b)).all()
               for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(q)))


def test_reference_agrees_with_the_program_forward(fam):
    import jax.numpy as jnp
    ref = harness.reference_module("qwen3")
    bundle = fam.program_bundle(CFG)
    p = fam.make_params(bundle, harness.jax_key(9))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 256, (2, 37)).astype(np.int32)
    want, _ = bundle.forward(p, {"tokens": jnp.asarray(toks)})
    want = np.asarray(want, np.float32)
    got = ref.logits_at(CFG, p, list(toks), [np.arange(37)] * 2)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5 * np.abs(w).max() + 1e-6)


def test_fp8_control_departs_from_the_reference(fam):
    ref = harness.reference_module("qwen3")
    bundle = fam.program_bundle(CFG)
    p = fam.make_params(bundle, harness.jax_key(5))
    rng = np.random.default_rng(0)
    reqs = [(rng.integers(0, 256, 40).astype(np.int32),
             rng.integers(0, 256, 24).astype(np.int32)) for _ in range(8)]
    seqs = [np.concatenate([a, s[:-1]]) for a, s in reqs]
    pos = [np.arange(39, 63)] * len(reqs)
    exact = ref.logits_at(CFG, p, seqs, pos)
    low = ref.logits_at(CFG, p, seqs, pos, quant="fp8")
    err = max(np.abs(a - b).max() for a, b in zip(exact, low))
    assert err > 1e-2


def test_mamba2_flop_counts_by_hand():
    m2 = harness.family_module("mamba2")
    cfg = smoke_root.SMOKE_MAMBA2
    # smoke sizes: L=2 D=64 Din=128 N=16 P=16 H=8 K=4 V=256
    in_proj = 64 * (2 * 128 + 2 * 16 + 8)
    per_layer = 2 * (in_proj + 128 * 64) + 2 * 4 * (128 + 2 * 16) \
        + 6 * 8 * 16 * 16
    assert m2.forward_flops_per_token(cfg) == 2 * per_layer + 2 * 64 * 256
    assert m2.train_flops_per_token(cfg) == 3 * (2 * per_layer
                                                 + 2 * 64 * 256)


def test_mamba2_counts_agree_with_the_program_parameter_tree():
    import jax
    m2 = harness.family_module("mamba2")
    full = harness.load_json(harness.config_path("mamba2-370m"))
    bundle = m2.program_bundle(full)
    tree = bundle.abstract_params()
    assert {x.dtype.name for x in jax.tree.leaves(tree)} == {"float32"}
    s = m2.sizes(full)
    L, D, Din, N, H, K, V = (s[k] for k in "L D Din N H K V".split())
    matrices = L * (D * (2 * Din + 2 * N + H) + Din * D) + 2 * V * D
    rest = L * (K * (Din + 2 * N) + (Din + 2 * N) + 3 * H + Din + D) + D
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert total == matrices + rest == 420_136_448     # 1.68 GB in f32
    flops = m2.forward_flops_per_token(full)     # the embedding is a gather
    assert 2 * (matrices - V * D) <= flops <= 1.2 * 2 * (matrices - V * D)


def test_mamba2_weights_follow_the_program_layout():
    import jax
    m2 = harness.family_module("mamba2")
    bundle = m2.program_bundle(smoke_root.SMOKE_MAMBA2)
    p = m2.make_params(bundle, harness.jax_key(2**33 + 7))
    want = bundle.abstract_params()
    assert jax.tree.structure(p) == jax.tree.structure(want)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in
               zip(jax.tree.leaves(p), jax.tree.leaves(want)))
    a = -np.exp(np.asarray(p["layers"]["A_log"]))
    assert (a <= -1).all() and (a >= -16).all()
    dt = np.log1p(np.exp(np.asarray(p["layers"]["dt_bias"])))
    assert (dt >= 1e-3 * 0.999).all() and (dt <= 0.1 * 1.001).all()
