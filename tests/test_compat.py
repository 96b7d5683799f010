"""Unit tests for the JAX portability layer (``repro.runtime.compat``).

Every helper is exercised against the installed JAX, so an upgrade that
moves one of the spellings fails here before it takes down the model zoo.
"""
import importlib
import pkgutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime import compat


# ---------------------------------------------------------------------------
# Mesh context: set/get round-trip
# ---------------------------------------------------------------------------

def test_make_mesh_axes_are_auto():
    """Explicit axes (JAX's make_mesh default since 0.7) reject the
    vocab-sharded embedding gather; compat meshes must be Auto."""
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    assert all(t == jax.sharding.AxisType.Auto for t in mesh.axis_types)


def test_mesh_context_round_trip():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    with compat.set_mesh(mesh):
        got = compat.get_abstract_mesh()
        assert got is not None and not getattr(got, "empty", False)
        assert tuple(got.axis_names) == ("data", "model")
        assert got.shape["model"] == 1 and got.shape["data"] == 1
    # context exit restores "no ambient mesh"
    after = compat.get_abstract_mesh()
    assert after is None or getattr(after, "empty", False)


def test_mesh_context_nests():
    m1 = compat.make_mesh((1, 1), ("data", "model"))
    m2 = compat.make_mesh((1,), ("model",))
    with compat.set_mesh(m1):
        with compat.set_mesh(m2):
            assert tuple(compat.get_abstract_mesh().axis_names) == ("model",)
        assert tuple(compat.get_abstract_mesh().axis_names) == (
            "data", "model")


def test_sharding_constraint_resolves_under_set_mesh():
    """Bare-PartitionSpec with_sharding_constraint (including
    UNCONSTRAINED dims, which Explicit axes refuse) must trace inside the
    compat mesh context."""
    from jax.sharding import PartitionSpec as P
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    x = jnp.ones((4, 8))
    with compat.set_mesh(mesh):
        y = jax.jit(lambda x: jax.lax.with_sharding_constraint(
            x, P("data", "model")))(x)
        y = jax.jit(lambda x: jax.lax.with_sharding_constraint(
            x, P(P.UNCONSTRAINED, "model")))(y)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


# ---------------------------------------------------------------------------
# shard_map
# ---------------------------------------------------------------------------

def test_shard_map_psum():
    from jax.sharding import PartitionSpec as P
    mesh = compat.make_mesh((1,), ("model",))
    fn = compat.shard_map(lambda x: jax.lax.psum(x, "model"), mesh=mesh,
                          in_specs=(P(),), out_specs=P())
    out = fn(jnp.arange(4.0))
    np.testing.assert_allclose(np.asarray(out), np.arange(4.0))


# ---------------------------------------------------------------------------
# vma typing: pcast / vma / match_vma
# ---------------------------------------------------------------------------

def test_pcast_identity_outside_shard_map():
    x = jnp.ones((3,))
    y = compat.pcast(x, (), to="varying")
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


def test_vma_and_match_vma_degenerate():
    x = jnp.ones((3,))
    assert isinstance(compat.vma(x), frozenset)
    y = compat.match_vma(jnp.zeros((3,)), x)   # same vma -> unchanged value
    np.testing.assert_array_equal(np.asarray(y), np.zeros((3,)))


# ---------------------------------------------------------------------------
# Pallas: element-indexed BlockSpec construction + numerics
# ---------------------------------------------------------------------------

def test_element_block_spec_constructs():
    spec = compat.element_block_spec(
        (compat.Element(8), 16), lambda i, j: (i * 8, j))
    from jax.experimental import pallas as pl
    assert isinstance(spec, pl.BlockSpec)


def test_element_block_spec_halo_numerics():
    """Overlapping (halo) windows via Element dims: out[i] = x[i] + x[i+1],
    computed with a 2-element element-indexed block per grid step."""
    from jax.experimental import pallas as pl
    n = 16
    x = np.arange(n + 1, dtype=np.float32)

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[:-1] + x_ref[1:]

    out = pl.pallas_call(
        kern, grid=(n // 4,),
        in_specs=[compat.element_block_spec(
            (compat.Element(5),), lambda i: (i * 4,))],
        out_specs=pl.BlockSpec((4,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.float32),
        interpret=True,
    )(x)
    np.testing.assert_allclose(np.asarray(out), x[:-1] + x[1:])


def test_element_marker_is_int():
    e = compat.Element(8)
    assert isinstance(e, int) and e == 8


# ---------------------------------------------------------------------------
# TPU compiler params
# ---------------------------------------------------------------------------

def test_compiler_params_resolution():
    from jax.experimental.pallas import tpu as pltpu
    kw = compat.tpu_compiler_params(
        dimension_semantics=("parallel", "arbitrary"))
    assert set(kw) == {"compiler_params"}
    assert isinstance(kw["compiler_params"], pltpu.CompilerParams)
    assert kw["compiler_params"].dimension_semantics == (
        "parallel", "arbitrary")


def test_compiler_params_unknown_kwarg_degrades():
    """A keyword the installed CompilerParams does not take is an error,
    never a silent drop of the kernel's compiler params."""
    with pytest.raises(TypeError):
        compat.tpu_compiler_params(definitely_not_a_real_kwarg=1)


# ---------------------------------------------------------------------------
# Scalar-prefetch grid spec (paged-attention page-table indirection)
# ---------------------------------------------------------------------------

def test_prefetch_scalar_grid_spec_gathers_by_table():
    """Index maps must see the prefetched scalar ref: a 2-page gather
    driven by a page table, in interpret mode."""
    from jax.experimental import pallas as pl

    def kern(pt_ref, x_ref, o_ref):
        o_ref[...] = x_ref[...]

    table = jnp.asarray([2, 0], jnp.int32)
    x = jnp.arange(4 * 8, dtype=jnp.float32).reshape(4, 8)
    spec = compat.prefetch_scalar_grid_spec(
        num_scalar_prefetch=1,
        grid=(2,),
        in_specs=[pl.BlockSpec((1, 8), lambda i, pt_ref: (pt_ref[i], 0))],
        out_specs=pl.BlockSpec((1, 8), lambda i, pt_ref: (i, 0)),
    )
    out = pl.pallas_call(
        kern, grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((2, 8), jnp.float32),
        interpret=True)(table, x)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(x[np.asarray(table)]))


# ---------------------------------------------------------------------------
# Import sweep: every repro.* module must import cleanly on this JAX
# ---------------------------------------------------------------------------

def _iter_repro_modules():
    import repro
    for info in pkgutil.walk_packages(repro.__path__, prefix="repro."):
        yield info.name


@pytest.mark.parametrize("mod", sorted(_iter_repro_modules()))
def test_module_imports_cleanly(mod):
    importlib.import_module(mod)


def test_no_direct_drift_api_call_sites():
    """The grep from the acceptance criteria, as a test: no module outside
    compat.py may touch the version-drifting spellings directly."""
    import pathlib
    root = pathlib.Path(__file__).resolve().parent.parent / "src" / "repro"
    banned = ("jax.set_mesh", "jax.sharding.get_abstract_mesh",
              "pl.Element(", "jax.lax.pcast", "jax.shard_map(")
    offenders = []
    for path in root.rglob("*.py"):
        if path.name == "compat.py":
            continue
        text = path.read_text()
        offenders += [f"{path.name}: {b}" for b in banned if b in text]
    assert not offenders, offenders


# ---------------------------------------------------------------------------
# Distributed runtime shim
# ---------------------------------------------------------------------------

def test_distributed_initialize_passes_extras_through_var_keyword(monkeypatch):
    calls = []

    def fake_init(coordinator_address, num_processes, process_id, **kw):
        calls.append(kw)

    monkeypatch.setattr(jax.distributed, "initialize", fake_init)
    assert compat.distributed_initialize("127.0.0.1:9999", 2, 0,
                                         cluster_detection_method="none")
    assert calls == [{"cluster_detection_method": "none"}]


def test_distributed_initialize_already_up_is_success(monkeypatch):
    def fake_init(**kw):
        raise RuntimeError("Distributed system is already initialized")

    monkeypatch.setattr(jax.distributed, "initialize", fake_init)
    assert compat.distributed_initialize("127.0.0.1:9999", 2, 0) is True


def test_distributed_initialize_degrades_to_warned_false(monkeypatch):
    def fake_init(**kw):
        raise RuntimeError("connection refused")

    monkeypatch.setattr(jax.distributed, "initialize", fake_init)
    with pytest.warns(RuntimeWarning, match="continuing single-process"):
        assert compat.distributed_initialize("127.0.0.1:9", 2, 0) is False


def test_distributed_shutdown_never_raises(monkeypatch):
    def boom():
        raise RuntimeError("not initialized")

    monkeypatch.setattr(jax.distributed, "shutdown", boom)
    compat.distributed_shutdown()  # must swallow
