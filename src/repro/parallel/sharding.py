"""Per-architecture sharding rules (DP / TP / EP / SP on the 2-3D mesh).

Megatron-style tensor parallelism on hidden dims — robust to head counts
that do not divide the mesh (qwen2.5/qwen1.5 have 40 heads on a 16-wide
model axis; hidden dims are all multiples of 16):
  * embed / lm_head: vocab on `model`
  * attention qkv: output features on `model`; wo: input features on `model`
  * mlp: w_gate/w_up features on `model`; w_down input on `model`
  * MoE: experts on `model` when divisible (EP), else per-expert ffn on
    `model` (TP-in-expert) — granite's 40 experts use the latter
  * activations / tokens: batch on `data` (+`pod` when multi-pod); the
    long-context batch=1 shapes shard sequence on `data` instead (SP)
  * KV caches: batch on `data`, head-dim features on `model` when the kv
    head count divides, else sequence on `model`
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _model_axis(mesh: Mesh) -> str:
    return "model"


def _data_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _div(n: int, mesh: Mesh, axis: str) -> bool:
    return n % mesh.shape[axis] == 0


def param_specs(arch_kind: str, params_shape: Any, mesh: Mesh) -> Any:
    """Build a PartitionSpec tree matching the param tree (by leaf path)."""
    m = _model_axis(mesh)

    def spec_for(path: tuple, leaf) -> P:
        names = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
        key = names[-1]
        shape = leaf.shape
        nd = len(shape)

        def last_on_model():
            return P(*([None] * (nd - 1) + [m]))

        def secondlast_on_model():
            return P(*([None] * (nd - 2) + [m, None]))

        if key in ("embed", "lm_head"):
            # embed (V, D): vocab on model; lm_head (D, V): vocab on model
            return P(m, None) if key == "embed" else P(None, m)
        if key in ("pos_dec",):
            return P(None, None)
        if key in ("wq", "wk", "wv", "w_x", "w_y", "in_proj",
                   "mlp_gate", "mlp_up", "mlp_w1"):
            return last_on_model()       # (..., D, F): F on model
        if key in ("bq", "bk", "bv", "mlp_b1", "b_in"):
            return last_on_model()
        if key == "w_down" and nd == 4:
            # moe (L, E, F, D): experts on model when divisible (EP),
            # else per-expert F on model (TP-in-expert)
            E = shape[1]
            if _div(E, mesh, m):
                return P(None, m, None, None)
            return P(None, None, m, None)
        if key in ("wo", "w_down", "mlp_down", "mlp_w2", "out_proj",
                   "w_out"):
            return secondlast_on_model()  # (..., F, D): F on model
        if key in ("w_gate", "w_up"):
            # dense mlp (L, D, F) -> F on model;
            # moe (L, E, D, F) -> E on model if divisible else F on model
            if nd == 4:
                E = shape[1]
                if _div(E, mesh, m):
                    return P(None, m, None, None)
                return P(None, None, None, m)
            return last_on_model()
        if key == "router":
            return P(None, None, None) if nd == 3 else P(None, None)
        if key in ("w_a", "w_i"):
            return last_on_model()       # (L, W, W) second W on model
        if key in ("conv_w", "conv_b", "A_log", "dt_bias", "D_skip", "lam",
                   "gnorm"):
            return P(*([None] * nd))
        # norms, biases, scalars: replicated
        return P(*([None] * nd))

    return jax.tree_util.tree_map_with_path(spec_for, params_shape)


def cache_specs(mesh: Mesh, *, kv_heads: int, head_dim: int,
                long_context: bool = False) -> dict[str, P]:
    """KV cache (L, B, S, Hkv, Dh) shardings."""
    m = _model_axis(mesh)
    d = _data_axes(mesh)
    if long_context:
        # batch=1: shard the cache sequence over data, features over model
        kv = P(None, None, d, None, m if head_dim % mesh.shape[m] == 0 else None)
    elif kv_heads % mesh.shape[m] == 0:
        kv = P(None, d, None, m, None)
    else:
        kv = P(None, d, None, None, m if head_dim % mesh.shape[m] == 0
               else None)
    return {"k": kv, "v": kv, "length": P(d)}


def paged_pool_specs(mesh: Mesh, *, kv_heads: int, head_dim: int) -> dict[str, P]:
    """Paged KV pool shardings (serving/kv.py block pool).

    Pool pages (L, P, page, Hkv, Dh) have no batch axis — the PAGE axis is
    the global one (any slot's table may point anywhere), so it shards over
    the data axes like the dense cache's batch does, while head structure
    follows the dense-cache rule: kv heads on `model` when divisible, else
    head_dim on `model` when divisible.  Page tables and lengths are tiny
    host-managed index state and stay replicated."""
    m = _model_axis(mesh)
    d = _data_axes(mesh)
    if kv_heads % mesh.shape[m] == 0:
        pages = P(None, d, None, m, None)
        scales = P(None, d, None, m)
    else:
        feat = m if head_dim % mesh.shape[m] == 0 else None
        pages = P(None, d, None, None, feat)
        scales = P(None, d, None, None)
    return {"k": pages, "v": pages, "k_scale": scales, "v_scale": scales,
            "page_table": P(None, None), "lengths": P(None)}


def make_shardings(tree_of_specs: Any, mesh: Mesh) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        tree_of_specs,
        is_leaf=lambda x: isinstance(x, P))
