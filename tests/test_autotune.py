"""Scheduler engine (repro.core.autotune): equivalence vs brute force,
pruning correctness and memoization keying."""
import pytest

from repro.core import (TEU_BUFFER, BufferSpec, attention_scores_op,
                        cache_stats, clear_cache, conv2d_op, correlation_op,
                        depthwise_conv2d_op, matmul_op, op_signature,
                        order_grid_for_sharing,
                        order_grid_for_sharing_reference, plan_mesh_exchange,
                        plan_mesh_exchange_reference, search_tiles,
                        search_tiles_reference)

FAMILIES = [
    ("matmul", lambda: matmul_op(256, 192, 320)),
    ("conv2d", lambda: conv2d_op(64, 32, 28, 28, 3, 3)),
    ("conv2d_strided", lambda: conv2d_op(8, 4, 10, 10, 3, 3,
                                         stride=2, dilation=2)),
    ("depthwise", lambda: depthwise_conv2d_op(64, 28, 28, 3, 3)),
    ("correlation", lambda: correlation_op(9, 9, 16, 16, 32)),
    ("attention", lambda: attention_scores_op(8, 128, 128, 64)),
]

BUFFERS = [
    TEU_BUFFER,
    BufferSpec(input_bytes=4 * 1024 * 1024, psum_bytes=1024 * 1024),
    BufferSpec(input_bytes=2048, psum_bytes=512),
]


@pytest.fixture(autouse=True)
def _fresh_cache():
    clear_cache()
    yield
    clear_cache()


@pytest.mark.parametrize("fam", [f[0] for f in FAMILIES])
def test_search_equivalent_to_reference(fam):
    """Engine returns byte-identical TileSchedules on every op family."""
    op = dict(FAMILIES)[fam]()
    for buf in BUFFERS:
        try:
            ref = search_tiles_reference(op, buf)
        except ValueError:
            with pytest.raises(ValueError):
                search_tiles(op, buf)
            continue
        eng = search_tiles(op, buf)
        assert eng == ref          # full dataclass: tile, bytes/MAC, grid, ...
        assert (eng.tile, eng.bytes_per_mac, eng.num_tiles) == \
               (ref.tile, ref.bytes_per_mac, ref.num_tiles)


@pytest.mark.parametrize("fam", [f[0] for f in FAMILIES])
def test_search_equivalent_with_caps_and_prefer_small(fam):
    op = dict(FAMILIES)[fam]()
    caps = {op.dims[0].name: max(1, op.dims[0].size // 4)}
    ref = search_tiles_reference(op, TEU_BUFFER, caps=caps, prefer_large=False)
    assert search_tiles(op, TEU_BUFFER, caps=caps, prefer_large=False) == ref


def test_search_equivalent_with_alignment():
    op = matmul_op(512, 512, 512)
    buf = BufferSpec(input_bytes=8 * 1024 * 1024, psum_bytes=4 * 1024 * 1024,
                     align={"i": 128, "j": 128})
    assert search_tiles(op, buf) == search_tiles_reference(op, buf)


@pytest.mark.parametrize("fam", [f[0] for f in FAMILIES])
def test_grid_order_equivalent(fam):
    op = dict(FAMILIES)[fam]()
    tile = search_tiles_reference(op, TEU_BUFFER).tile
    assert order_grid_for_sharing(op, tile) == \
        order_grid_for_sharing_reference(op, tile)


@pytest.mark.parametrize("fam", [f[0] for f in FAMILIES])
def test_mesh_exchange_equivalent(fam):
    op = dict(FAMILIES)[fam]()
    tile = search_tiles_reference(op, TEU_BUFFER).tile
    for mesh in ((2, 2), (4, 4), (8, 2)):
        assert plan_mesh_exchange(op, tile, mesh) == \
            plan_mesh_exchange_reference(op, tile, mesh)
    assert plan_mesh_exchange(op, tile, (4, 4), share_cols=False,
                              col_span_cap=3) == \
        plan_mesh_exchange_reference(op, tile, (4, 4), share_cols=False,
                                     col_span_cap=3)


def test_structural_twins_share_cache_entry():
    """Two structurally-identical ops built separately hit one entry."""
    a = conv2d_op(32, 16, 14, 14, 3, 3)
    b = conv2d_op(32, 16, 14, 14, 3, 3, name="other_conv")
    assert op_signature(a) == op_signature(b)
    s1 = search_tiles(a, TEU_BUFFER)
    misses = cache_stats["misses"]
    s2 = search_tiles(b, TEU_BUFFER)
    assert cache_stats["misses"] == misses     # second call: pure cache hit
    assert cache_stats["hits"] >= 1
    assert s2.tile == s1.tile
    # the cached schedule is re-labelled with the caller's op name
    assert s1.op_name == "conv2d" and s2.op_name == "other_conv"


def test_different_structure_different_entry():
    s1 = search_tiles(matmul_op(128, 128, 128), TEU_BUFFER)
    misses = cache_stats["misses"]
    s2 = search_tiles(matmul_op(128, 128, 256), TEU_BUFFER)
    assert cache_stats["misses"] == misses + 1
    assert s1.tile != s2.tile or s1.num_tiles != s2.num_tiles


def test_buffer_and_caps_in_cache_key():
    op = matmul_op(256, 256, 256)
    search_tiles(op, TEU_BUFFER)
    misses = cache_stats["misses"]
    search_tiles(op, BufferSpec(input_bytes=1 << 20, psum_bytes=1 << 18))
    search_tiles(op, TEU_BUFFER, caps={"i": 16})
    assert cache_stats["misses"] == misses + 2


def test_engine_infeasible_raises_like_reference():
    op = matmul_op(8, 8, 8)
    with pytest.raises(ValueError):
        search_tiles(op, BufferSpec(input_bytes=4, psum_bytes=1))
