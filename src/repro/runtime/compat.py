"""JAX portability layer, written against the installed JAX (0.9.0).

The rest of the code base takes its mesh, ``shard_map``, varying-axes
typing and Pallas TPU helpers from here, and ``tests/test_compat.py``
greps that no other module spells them directly.  A JAX upgrade that
moves one of these symbols is then repaired in this file alone.

Two choices here are not the installed JAX's defaults:

* :func:`make_mesh` builds meshes with ``AxisType.Auto`` axes.  Since
  0.7 ``jax.make_mesh`` defaults to Explicit axes, under which a gather
  from a vocab-sharded embedding table or a scatter into a sharded page
  pool raises ``ShardingTypeError`` and ``PartitionSpec.UNCONSTRAINED``
  is refused.  Every sharding in this repo is written for GSPMD
  propagation (Auto).
* :func:`tpu_compiler_params` raises on a keyword the installed
  ``pltpu.CompilerParams`` does not take: kernel compiler parameters
  never vanish silently.

Pallas is imported lazily, so entry points that never touch a kernel do
not pay for it.  Nothing here touches device state, so importing this
module cannot pin a backend.
"""
from __future__ import annotations

import warnings
from typing import Any, Callable, Sequence

import jax

__all__ = [
    "make_mesh", "set_mesh", "get_abstract_mesh", "shard_map",
    "pcast", "vma", "match_vma",
    "Element", "element_block_spec", "prefetch_scalar_grid_spec",
    "tpu_compiler_params", "memory_stats", "tpu_chips_attached",
    "distributed_initialize", "distributed_shutdown",
]


# ---------------------------------------------------------------------------
# Meshes and the ambient-mesh context
# ---------------------------------------------------------------------------

def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto`` (see above).
    ``devices`` defaults to ``jax.devices()``."""
    auto = (jax.sharding.AxisType.Auto,) * len(axis_names)
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=auto, devices=devices)


set_mesh = jax.set_mesh
get_abstract_mesh = jax.sharding.get_abstract_mesh
shard_map = jax.shard_map


# ---------------------------------------------------------------------------
# Varying-manual-axes (vma) typing: pcast / vma / match_vma
# ---------------------------------------------------------------------------

pcast = jax.lax.pcast


def vma(x) -> frozenset:
    """The varying manual axes of ``x``'s type (empty outside shard_map)."""
    return frozenset(jax.typeof(x).vma)


def match_vma(x, like):
    """Promote ``x`` to carry every varying axis ``like`` carries.

    Inside shard_map, scan/loop carries and ``pallas_call`` out-shapes
    must be typed with the same varying axes as the values they combine
    with."""
    want = vma(like) - vma(x)
    if want:
        x = pcast(x, tuple(want), to="varying")
    return x


# ---------------------------------------------------------------------------
# Pallas: element-indexed BlockSpecs
# ---------------------------------------------------------------------------

class Element(int):
    """Marker for a block dim whose index-map output is an ELEMENT offset
    (halo/overlapping windows), not a block index.  Use only inside
    :func:`element_block_spec` block shapes."""


def element_block_spec(block_shape: Sequence[int],
                       index_map: Callable[..., tuple]):
    """BlockSpec mixing :class:`Element` (element-indexed) and plain int
    (block-indexed) dims.  ``index_map`` returns element offsets for
    Element dims and block indices for the rest."""
    from jax.experimental import pallas as pl
    shape = tuple(pl.Element(int(d)) if isinstance(d, Element) else d
                  for d in block_shape)
    return pl.BlockSpec(shape, index_map)


# ---------------------------------------------------------------------------
# Pallas: scalar-prefetch grid specs and compiler params
# ---------------------------------------------------------------------------

def prefetch_scalar_grid_spec(*, num_scalar_prefetch: int, grid,
                              in_specs, out_specs, scratch_shapes=()):
    """Grid spec whose first ``num_scalar_prefetch`` operands are scalar
    arrays prefetched before the kernel runs and passed to every index map
    (trailing arguments) and to the kernel body (leading refs).  This is
    the mechanism behind page-table indirection in the paged-attention
    kernel and the pair-table schedule of the flash kernels."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch, grid=grid,
        in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=scratch_shapes)


def tpu_compiler_params(**kwargs) -> dict[str, Any]:
    """``{"compiler_params": pltpu.CompilerParams(**kwargs)}`` to splat
    into ``pl.pallas_call``.  An unknown keyword raises ``TypeError``."""
    from jax.experimental.pallas import tpu as pltpu
    return {"compiler_params": pltpu.CompilerParams(**kwargs)}


# ---------------------------------------------------------------------------
# Compiled-artifact introspection
# ---------------------------------------------------------------------------

def memory_stats(compiled) -> dict[str, int]:
    """Normalized ``Compiled.memory_analysis()`` numbers, in bytes.

    Always returns ``{"argument_bytes", "output_bytes", "temp_bytes",
    "peak_bytes"}`` with zeros when the backend offers no analysis or an
    attribute is missing.  ``peak_bytes`` is arguments + temporaries:
    donated outputs alias their inputs on TPU, so args+temp approximates
    the device peak (the CPU backend ignores donation, hence not
    args+temp+out)."""
    try:
        mem = compiled.memory_analysis()
    except Exception:  # noqa: BLE001 — backend without the analysis
        mem = None

    def _get(name: str) -> int:
        return int(getattr(mem, name, 0) or 0)

    arg = _get("argument_size_in_bytes")
    out = _get("output_size_in_bytes")
    tmp = _get("temp_size_in_bytes")
    return {"argument_bytes": arg, "output_bytes": out,
            "temp_bytes": tmp, "peak_bytes": arg + tmp}


def tpu_chips_attached() -> int:
    """TPU chips on this host's PCI bus, found without initializing a
    backend — so a parent process can ask before it starts JAX children
    (a chip belongs to one process at a time)."""
    from jax._src import hardware_utils
    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


# ---------------------------------------------------------------------------
# Distributed runtime: multi-process peers
# ---------------------------------------------------------------------------

def distributed_initialize(coordinator_address: str, num_processes: int,
                           process_id: int, *,
                           timeout_s: float | None = None,
                           **extra) -> bool:
    """Bring up the multi-process runtime; ``True`` iff peers are joined.

    Degrades to a warned ``False`` on any failure — callers treat the
    distributed runtime as an upgrade, not a requirement.  A second call
    in an already-initialized process returns ``True``.  The coordinator
    address, process count and id are always passed explicitly, so JAX
    never looks for a cluster metadata service.
    """
    kwargs: dict[str, Any] = {"coordinator_address": coordinator_address,
                              "num_processes": int(num_processes),
                              "process_id": int(process_id), **extra}
    if timeout_s is not None:
        kwargs["initialization_timeout"] = int(timeout_s)
    try:
        jax.distributed.initialize(**kwargs)
        return True
    except Exception as e:  # noqa: BLE001 — availability probe by contract
        if "already" in str(e).lower():
            return True
        warnings.warn(f"jax distributed runtime failed to initialize "
                      f"({type(e).__name__}: {e}); continuing single-process",
                      RuntimeWarning)
        return False


def distributed_shutdown() -> None:
    """Best-effort ``jax.distributed.shutdown`` (no-op when never up)."""
    try:
        jax.distributed.shutdown()
    except Exception:  # noqa: BLE001 — teardown must never mask exit status
        pass
