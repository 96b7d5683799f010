"""Mamba-2 (SSD): the program's bundle for a configuration file, seeded
weights in the program's parameter layout, and operation counts from
shapes.

The weights are made here, from the run's seed, on the device in one
jitted call, in the type they are trained in: the program's own
initialiser is never called, so the reference sees weights that the
program did not make.
"""
from __future__ import annotations

import math

MATRIX_STD = 0.02     # projections, embedding, head
CONV_STD = 0.2        # depthwise conv taps
NORM_STD = 0.1        # gains drawn around 1, so a gain read wrongly shows


def sizes(config: dict) -> dict:
    c, m = config["config"], config["mamba2_layer"]
    D = c["d_model"]
    Din = m["expand"] * D
    return {"L": c["n_layer"], "D": D, "Din": Din, "N": m["d_state"],
            "P": m["headdim"], "H": Din // m["headdim"], "K": m["d_conv"],
            "V": c["vocab_size"], "chunk": m["chunk_size"],
            "eps": float(m["norm_eps"])}


def program_bundle(config: dict):
    """The program's bundle for this configuration, in the file's dtype;
    refuses one whose sizes differ from the file's, so the file is what
    runs."""
    import dataclasses

    import jax.numpy as jnp

    from repro.configs import get_bundle
    bundle = get_bundle(config["arch_id"],
                        smoke=config.get("program_preset") == "smoke")
    p = dataclasses.replace(bundle.cfg, dtype=getattr(jnp, config["dtype"]))
    s = sizes(config)
    got = {"L": p.n_layers, "D": p.d_model, "Din": p.d_inner,
           "N": p.d_state, "P": p.headdim, "H": p.n_heads, "K": p.d_conv,
           "V": p.vocab, "chunk": p.chunk, "eps": float(p.norm_eps)}
    bad = {k: (got[k], s[k]) for k in s if got[k] != s[k]}
    if config["mamba2_layer"]["ngroups"] != 1:
        bad["ngroups"] = (1, config["mamba2_layer"]["ngroups"])
    if bad:
        raise ValueError(f"program config differs from the file: {bad}")
    return dataclasses.replace(bundle, cfg=p)


def make_params(bundle, key):
    """Seeded weights with the program's tree structure, shapes and
    dtypes (see the configuration's ``assumed.weights``)."""
    import jax
    import jax.numpy as jnp

    abstract = bundle.abstract_params()
    paths = jax.tree_util.tree_flatten_with_path(abstract)[0]
    treedef = jax.tree_util.tree_structure(abstract)
    lo, hi = math.log(1e-3), math.log(1e-1)

    def draw(k):
        keys = jax.random.split(k, len(paths))
        leaves = []
        for kk, (path, leaf) in zip(keys, paths):
            name = jax.tree_util.keystr(path)
            kn, ku = jax.random.split(kk)
            z = jax.random.normal(kn, leaf.shape, jnp.float32)
            u = jax.random.uniform(ku, leaf.shape, jnp.float32)
            if "A_log" in name:
                x = jnp.log(1.0 + 15.0 * u)
            elif "dt_bias" in name:
                dt = jnp.exp(lo + (hi - lo) * u)
                x = dt + jnp.log(-jnp.expm1(-dt))     # softplus^-1
            elif "D_skip" in name:
                x = jnp.ones(leaf.shape, jnp.float32)
            elif "conv_b" in name:
                x = jnp.zeros(leaf.shape, jnp.float32)
            elif "conv_w" in name:
                x = CONV_STD * z
            elif "norm" in name or "ln" in name:
                x = 1.0 + NORM_STD * z
            else:
                x = MATRIX_STD * z
            leaves.append(x.astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(draw)(key)


# ---------------------------------------------------------------------------
# operations, from shapes
# ---------------------------------------------------------------------------

def forward_flops_per_token(config: dict) -> float:
    """Model FLOPs of one token's forward pass: 2 per multiply-add of the
    input and output projections and the head, the depthwise conv, and the
    SSD recurrence (per head: decay and input into the P x N state, and
    the output read from it: 3 multiply-adds per state element)."""
    s = sizes(config)
    L, D, Din, N, H, P, K, V = (s[k] for k in "L D Din N H P K V".split())
    in_proj = D * (2 * Din + 2 * N + H)
    per_layer = 2 * (in_proj + Din * D) + 2 * K * (Din + 2 * N) \
        + 6 * H * P * N
    return L * per_layer + 2.0 * D * V


def train_flops_per_token(config: dict) -> float:
    """Forward and backward (twice the forward), without recomputation."""
    return 3.0 * forward_flops_per_token(config)
