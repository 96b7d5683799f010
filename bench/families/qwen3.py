"""Qwen3 dense decoder: the program's bundle for a configuration file,
seeded weights in the program's parameter layout, and operation and byte
counts from shapes.

The weights are made here, from the run's seed, on the device in one
jitted call, in the type they are served in: the program's own
initialiser is never called, so the reference sees weights that the
program did not make.
"""
from __future__ import annotations

MATRIX_STD = 0.02     # initializer_range of the published config
NORM_STD = 0.1        # norm gains drawn around 1, so a gain read wrongly shows


def sizes(config: dict) -> dict:
    c = config["config"]
    return {"L": c["num_hidden_layers"], "D": c["hidden_size"],
            "H": c["num_attention_heads"], "Kv": c["num_key_value_heads"],
            "Dh": c["head_dim"], "F": c["intermediate_size"],
            "V": c["vocab_size"]}


def program_bundle(config: dict):
    """The program's bundle for this configuration; refuses one whose
    sizes differ from the file's, so the file is what runs."""
    from repro.configs import get_bundle
    bundle = get_bundle(config["arch_id"],
                        smoke=config.get("program_preset") == "smoke")
    p, s, c = bundle.cfg, sizes(config), config["config"]
    got = {"L": p.n_layers, "D": p.d_model, "H": p.n_heads,
           "Kv": p.n_kv_heads, "Dh": p.dh, "F": p.d_ff, "V": p.vocab}
    extra = {"rope_theta": (float(p.rope_theta), float(c["rope_theta"])),
             "rms_norm_eps": (float(p.norm_eps), float(c["rms_norm_eps"])),
             "qk_norm": (bool(p.qk_norm), True),
             "attention_bias": (bool(p.qkv_bias), c["attention_bias"])}
    bad = {k: (got[k], s[k]) for k in s if got[k] != s[k]}
    bad.update({k: v for k, v in extra.items() if v[0] != v[1]})
    if bad:
        raise ValueError(f"program config differs from the file: {bad}")
    return bundle


class ServingAdapter:
    """The serving engine's view of a bundle (hashable, so the engine's
    jitted step can take ``paged_step`` as a static argument)."""

    def __init__(self, bundle):
        self.bundle = bundle
        self.cfg = bundle.cfg
        self.kind = bundle.kind
        self.supports_paged_kv = bundle.supports_paged_kv
        self.prefill_supports_true_lengths = \
            bundle.prefill_supports_true_lengths

    def init_paged_pool(self, num_pages, page_size, kv_dtype=None):
        return self.bundle.init_paged_pool(num_pages, page_size,
                                           kv_dtype=kv_dtype)

    def paged_step(self, params, tokens, pool, page_table, lengths, counts):
        return self.bundle.paged_step(params, tokens, pool, page_table,
                                      lengths, counts)


def make_params(bundle, key):
    """Seeded weights with the program's tree structure, shapes and
    dtypes: norm gains ~ 1 + 0.1 N(0, 1), every other leaf 0.02 N(0, 1)."""
    import jax
    import jax.numpy as jnp

    abstract = bundle.abstract_params()
    paths = jax.tree_util.tree_flatten_with_path(abstract)[0]
    treedef = jax.tree_util.tree_structure(abstract)

    def draw(k):
        keys = jax.random.split(k, len(paths))
        leaves = []
        for kk, (path, leaf) in zip(keys, paths):
            name = jax.tree_util.keystr(path)
            z = jax.random.normal(kk, leaf.shape, jnp.float32)
            if "norm" in name or "ln" in name:
                x = 1.0 + NORM_STD * z
            else:
                x = MATRIX_STD * z
            leaves.append(x.astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(draw)(key)


# ---------------------------------------------------------------------------
# operations and bytes, from shapes
# ---------------------------------------------------------------------------

def linear_params(config: dict) -> int:
    """Weights every computed token multiplies: the layers' projections
    and MLP, and the output head (the embedding is a gather)."""
    s = sizes(config)
    L, D, H, Kv, Dh, F, V = (s[k] for k in "L D H Kv Dh F V".split())
    per_layer = D * H * Dh + 2 * D * Kv * Dh + H * Dh * D + 3 * D * F
    return L * per_layer + D * V


def token_flops(config: dict, tokens: int, attended: int) -> float:
    """Model FLOPs of ``tokens`` computed tokens that attend ``attended``
    keys in all (summed over the tokens): 2 per multiply-add of the
    linear weights, and 4 * Dh per attended key and query head (scores
    and the weighted sum of values) in every layer."""
    s = sizes(config)
    return (2.0 * linear_params(config) * tokens
            + 4.0 * s["L"] * s["H"] * s["Dh"] * attended)


def paged_decode_cost(config: dict, rows: int, attended: int,
                      kv_itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) the paged decode attention needs for ``rows`` query
    rows attending ``attended`` cached keys in all, over every layer:
    each attended key's K and V rows are read once, each row's q is read
    and its output written once."""
    s = sizes(config)
    L, H, Kv, Dh = s["L"], s["H"], s["Kv"], s["Dh"]
    flops = 4.0 * L * H * Dh * attended
    kv = 2.0 * L * Kv * Dh * kv_itemsize * attended
    qo = 2.0 * L * rows * H * Dh * kv_itemsize
    return flops, kv + qo
