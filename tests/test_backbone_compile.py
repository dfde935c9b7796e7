"""The sequence backbone's kernels and the fused top-k kernel compiled
for a TPU v5e at the widths the benchmark runs, with no chip: the
chip's own compiler is installed here and compiles for a described
device (nothing runs, so these say nothing of results or times). What
interpret mode cannot show: a block Mosaic refuses, more fast memory
than a kernel may use, a catalog the call copies before it reads it.

All of them live in this one file, and the topology is described inside
a fixture: only the worker that is handed this file loads the TPU's
library."""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """The kernels ask the backend whether to run interpreted; the
    compile for the described chip must not."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compiled_kernels(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("tokens", [512, 8192])
@pytest.mark.parametrize("kv_heads,window", [(4, None), (8, 128)])
def test_packed_attention_compiles_at_width(one_chip, as_tpu, tokens,
                                            kv_heads, window):
    from predictionio_tpu.ops.attention import packed_attention

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def f(q, k, v, seg, start, sink):
        return packed_attention(q, k, v, seg, start, window=window,
                                sink=sink if window else None,
                                max_segment=2048)

    compiled = jax.jit(f).lower(
        sds((tokens, 64, 192), jnp.bfloat16),
        sds((tokens, kv_heads, 192), jnp.bfloat16),
        sds((tokens, kv_heads, 128), jnp.bfloat16),
        sds((tokens,), jnp.int32), sds((tokens,), jnp.int32),
        sds((64,), jnp.float32)).compile()
    assert _compiled_kernels(compiled) == 1


@pytest.mark.parametrize("k_dim,n_dim", [(4096, 4096), (2048, 4096)])
def test_grouped_matmul_compiles_at_width(one_chip, as_tpu, k_dim, n_dim):
    from predictionio_tpu.ops.moe import grouped_matmul

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows, tm = 12288, 256
    compiled = jax.jit(
        lambda x, w, e, n: grouped_matmul(x, w, e, n, tm, 512,
                                          jnp.float32)).lower(
        sds((rows, k_dim), jnp.bfloat16),
        sds((16, k_dim, n_dim), jnp.bfloat16),
        sds((rows // tm,), jnp.int32), sds((), jnp.int32)).compile()
    assert _compiled_kernels(compiled) == 1


def test_packed_attention_compiles_at_lfm2s_width(one_chip, as_tpu):
    """LFM2-8B-A1B: 32 query heads on 8 KV heads (group 4), q, k and v
    of 64, no window, no sink, 8,192 tokens."""
    from predictionio_tpu.ops.attention import (
        packed_attention, packed_block_sizes)
    assert packed_block_sizes(8192, 4) == (512, 256)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda q, k, v, seg, start: packed_attention(
            q, k, v, seg, start, max_segment=2048)).lower(
        sds((8192, 32, 64), jnp.bfloat16), sds((8192, 8, 64), jnp.bfloat16),
        sds((8192, 8, 64), jnp.bfloat16),
        sds((8192,), jnp.int32), sds((8192,), jnp.int32)).compile()
    assert _compiled_kernels(compiled) == 1


@pytest.mark.parametrize("k_dim,n_dim", [(2048, 3584), (1792, 2048)])
def test_grouped_matmul_compiles_at_lfm2s_width(one_chip, as_tpu, k_dim,
                                                n_dim):
    """All 32 experts of width 1,792 (gate beside up: 3,584), one
    buffer of every pair of an 8,192-token call in 256-row blocks."""
    from predictionio_tpu.ops.moe import (
        buffer_pairs, grouped_matmul, moe_block_rows)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tm = moe_block_rows(8192)
    rows = buffer_pairs(8192, 4, 32, 32) + 32 * tm
    assert (tm, rows) == (256, 40960)
    compiled = jax.jit(
        lambda x, w, e, n: grouped_matmul(x, w, e, n, tm, 512,
                                          jnp.float32)).lower(
        sds((rows, k_dim), jnp.bfloat16),
        sds((32, k_dim, n_dim), jnp.bfloat16),
        sds((rows // tm,), jnp.int32), sds((), jnp.int32)).compile()
    assert _compiled_kernels(compiled) == 1


@pytest.mark.parametrize("width,ffn,held,n_experts,top_k,scatters", [
    (2048, 1792, 32, 32, 4, 0),      # lfm2-8b-a1b-pp2-12l: the gather
    (4096, 2048, 16, 256, 8, 1),     # mimo-v2.5-ep16-7l: the scatter-add
])
def test_expert_layer_combines_by_gather_where_a_buffer_holds_every_pair(
        one_chip, as_tpu, width, ffn, held, n_experts, top_k, scatters):
    """`moe_apply` at 8,192 tokens: two grouped products, and a float32
    scatter of whole rows onto the tokens only where a buffer holds
    fewer pairs than the call may bring."""
    from predictionio_tpu.ops import moe

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    T = 8192
    assert moe.gather_combine(T, top_k, held, n_experts) == (not scatters)
    compiled = jax.jit(
        lambda u, e, w, wgu, wd, live: moe.moe_apply(
            u, moe.Routing(e, w), wgu, wd, first=0, n_experts=n_experts,
            live=live)).lower(
        sds((T, width), jnp.float32), sds((T, top_k), jnp.int32),
        sds((T, top_k), jnp.float32),
        sds((held, width, 2 * ffn), jnp.bfloat16),
        sds((held, ffn, width), jnp.bfloat16), sds((T,), jnp.bool_)).compile()
    assert _compiled_kernels(compiled) == 2
    row_scatters = re.findall(
        rf"= f32\[{T},{width}\]\S* scatter\(", compiled.as_text())
    assert len(row_scatters) == scatters


@pytest.mark.parametrize("tokens", [512, 8192])
def test_ssm_chunk_scan_compiles_at_nemotrons_width(one_chip, as_tpu,
                                                    tokens):
    """Nemotron-H's Mamba-2 mixer: 64 heads of 64 in 8 groups, state
    128, chunks of 128; bfloat16 operands, float32 steps and result.
    The kernel slices a group's 8 heads at 64-lane offsets and carries
    their states [8, 128, 64] in fast memory."""
    from predictionio_tpu.ops import ssm

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda x, dt, a, b, c, first: ssm.chunk_scan(
            x, dt, a, b, c, first, 128)).lower(
        sds((tokens, 64, 64), jnp.bfloat16), sds((tokens, 64), jnp.float32),
        sds((64,), jnp.float32), sds((tokens, 8, 128), jnp.bfloat16),
        sds((tokens, 8, 128), jnp.bfloat16),
        sds((tokens,), jnp.bool_)).compile()
    assert _compiled_kernels(compiled) == 1


def test_packed_attention_compiles_at_nemotrons_width(one_chip, as_tpu):
    """32 query heads on 2 KV heads (a group of 16) of 128, no window,
    no sink, 8,192 tokens: 128 query tokens a block."""
    from predictionio_tpu.ops.attention import (
        packed_attention, packed_block_sizes)
    assert packed_block_sizes(8192, 16) == (128, 256)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda q, k, v, seg, start: packed_attention(
            q, k, v, seg, start, max_segment=2048)).lower(
        sds((8192, 32, 128), jnp.bfloat16), sds((8192, 2, 128), jnp.bfloat16),
        sds((8192, 2, 128), jnp.bfloat16),
        sds((8192,), jnp.int32), sds((8192,), jnp.int32)).compile()
    assert _compiled_kernels(compiled) == 1


def test_half_the_squared_relu_experts_combine_by_gather(one_chip, as_tpu):
    """`moe_apply` at 8,192 tokens with 64 of 128 two-matrix experts of
    width 1,856 over a hidden size of 2,688, top 6: one buffer of every
    pair, two grouped products (the up product's 1,856 columns are 14.5
    lane groups and go as one tile; the down product's 2,688 in tiles
    of 384), no scatter of rows and no copy of the weights."""
    from predictionio_tpu.ops import moe

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    T, D, F, held, n_experts, top_k = 8192, 2688, 1856, 64, 128, 6
    assert moe.gather_combine(T, top_k, held, n_experts)
    assert moe.buffer_pairs(T, top_k, held, n_experts) == T * top_k
    assert (moe.col_block(F, 512), moe.col_block(D, 512),
            moe.col_block(3584, 512), moe.col_block(64, 512)) == (
                F, 384, 512, 64)
    compiled = jax.jit(
        lambda u, e, w, w_up, w_down, live: moe.moe_apply(
            u, moe.Routing(e, w), w_up, w_down, first=0,
            n_experts=n_experts, live=live)).lower(
        sds((T, D), jnp.float32), sds((T, top_k), jnp.int32),
        sds((T, top_k), jnp.float32), sds((held, D, F), jnp.bfloat16),
        sds((held, F, D), jnp.bfloat16), sds((T,), jnp.bool_)).compile()
    assert _compiled_kernels(compiled) == 2
    assert not re.findall(rf"= f32\[{T},{D}\]\S* scatter\(",
                          compiled.as_text())
    # neither product re-lays its weights: the compiler keeps w_up
    # [64, 2688, 1856] with the 2,688 on the lanes, and the kernel is
    # handed its transpose (`moe.columns_first`)
    assert moe.columns_first(F) and not moe.columns_first(D)
    assert _moves_of(compiled, held * D * F) == []


def _moves_of(compiled, cells: int) -> list:
    """The compiled program's `copy` and `transpose` instructions whose
    result holds at least `cells` elements: a catalog that the call
    re-lays before the kernel reads it."""
    found = []
    for line in compiled.as_text().splitlines():
        m = re.search(r"= \w+\[([\d,]+)\]\S* (copy|transpose)\(", line)
        if m and math.prod(int(d) for d in m.group(1).split(",")) >= cells:
            found.append(line.strip()[:200])
    return found


def _assert_reads_the_catalog_where_it_lies(compiled, n_rows, rank):
    assert _compiled_kernels(compiled) == 1
    assert _moves_of(compiled, n_rows * rank) == []
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 0.05 * n_rows * rank * 4


def _compiled_fused_bucket(one_chip, monkeypatch, n_items, rank, bucket):
    """The single-device fused call over `[n_items, rank]`, compiled
    for the described chip."""
    from predictionio_tpu.ops import fused_topk
    monkeypatch.setattr(fused_topk, "interpreted", lambda: False)
    call = fused_topk._pallas_topk(n_items, rank, k=10, bucket=bucket,
                                   banned_width=64, n_valid=n_items)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return jax.jit(call).lower(
        sds((bucket, rank), jnp.float32), sds((n_items, rank), jnp.float32),
        sds((bucket, 64), jnp.int32)).compile()


@pytest.mark.parametrize("bucket", [1, 64])
@pytest.mark.parametrize("n_items", [12_047_500, 24_095_000])
def test_fused_topk_reads_the_rank64_catalog_where_it_lies(
        one_chip, monkeypatch, n_items, bucket):
    """The quarter and the half of the Amazon-23 catalog at rank 64:
    the compiler keeps `f32[n, 64]` with the items on the lanes, the
    kernel takes `(64, tile)` blocks of its transpose, and the call
    holds no copy of the catalog (the half catalog with one ended in
    RESOURCE_EXHAUSTED)."""
    from predictionio_tpu.ops import fused_topk
    assert fused_topk._items_on_lanes(64)
    compiled = _compiled_fused_bucket(one_chip, monkeypatch, n_items, 64,
                                      bucket)
    _assert_reads_the_catalog_where_it_lies(compiled, n_items, 64)


@pytest.mark.parametrize("bucket", [1, 64])
def test_sharded_fused_topk_reads_each_shard_where_it_lies(
        topo, monkeypatch, bucket):
    """`ShardedBucketedTopK`'s program over the 2x2 host, 12,047,500
    rows of 64 a chip: the per-shard kernel inside the shard_map reads
    its `[per_shard, 64]` block with no copy of it."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from predictionio_tpu.ops import fused_topk
    from predictionio_tpu.ops.topk_sharded import (
        SHARD_AXIS, ShardedBucketedTopK)
    monkeypatch.setattr(fused_topk, "interpreted", lambda: False)
    monkeypatch.setenv("PIO_SERVE_FUSED", "on")
    per, rank, n_shards = 12_047_500, 64, len(topo.devices)
    mesh = Mesh(np.array(topo.devices), (SHARD_AXIS,))
    # the plan's own program, without its constructor's device_put
    # (a described device holds no array)
    plan = ShardedBucketedTopK.__new__(ShardedBucketedTopK)
    plan.mesh, plan.n_shards, plan.rank = mesh, n_shards, rank
    plan.per_shard, plan.n_items = per, per * n_shards - 3
    plan.k = plan.k_shard = 10
    plan.banned_width = 64
    plan._fused_sizes = set()

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    compiled = plan._build(bucket=bucket).lower(
        sds((bucket, rank), jnp.float32, P()),
        sds((per * n_shards, rank), jnp.float32, P(SHARD_AXIS, None)),
        sds((bucket, 64), jnp.int32, P())).compile()
    assert plan.fused_buckets == 1
    _assert_reads_the_catalog_where_it_lies(compiled, per, rank)


@pytest.mark.parametrize("bucket", [1, 64])
def test_fused_topk_compiles_over_the_heads_rows(one_chip, monkeypatch,
                                                 bucket):
    """19,072 rows of 4,096: the tile is cut to fit the fast memory
    (rank 64 keeps its 4,096-row tile), and rows of whole lane groups
    keep their `(tile, rank)` blocks, which read them with no copy."""
    from predictionio_tpu.ops import fused_topk
    assert fused_topk._tile_items(19072, 10, 4096) == (256, 256)
    assert fused_topk._tile_items(12_047_500, 10, 64) == (4096, 1024)
    assert not fused_topk._items_on_lanes(4096)
    compiled = _compiled_fused_bucket(one_chip, monkeypatch, 19072, 4096,
                                      bucket)
    _assert_reads_the_catalog_where_it_lies(compiled, 19072, 4096)


@pytest.mark.parametrize("bucket", [1, 64])
def test_fused_topk_compiles_over_the_whole_lfm2_vocabulary(
        one_chip, monkeypatch, bucket):
    """65,536 rows of 2,048 (the tied table, float32 for the plan):
    512-row tiles of whole lane groups, read where they lie."""
    from predictionio_tpu.ops import fused_topk
    assert fused_topk._tile_items(65536, 10, 2048) == (512, 512)
    assert not fused_topk._items_on_lanes(2048)
    compiled = _compiled_fused_bucket(one_chip, monkeypatch, 65536, 2048,
                                      bucket)
    _assert_reads_the_catalog_where_it_lies(compiled, 65536, 2048)


@pytest.mark.parametrize("bucket", [1, 32, 64])
def test_fused_topk_compiles_over_nemotrons_held_rows(one_chip, monkeypatch,
                                                      bucket):
    """65,536 rows of 2,688 (21 lane groups, no power of two; the head,
    float32 for the plan), read where they lie, in tiles of 384: whole
    lane groups inside the tile's bytes (512 rows ran the bucket of 32,
    and no other, out of fast memory on the chip: my chip run, PR 36)."""
    from predictionio_tpu.ops import fused_topk
    assert fused_topk._tile_items(65536, 10, 2688) == (384, 384)
    assert not fused_topk._items_on_lanes(2688)
    compiled = _compiled_fused_bucket(one_chip, monkeypatch, 65536, 2688,
                                      bucket)
    _assert_reads_the_catalog_where_it_lies(compiled, 65536, 2688)
