"""The sequence backbone's kernels compiled for a TPU v5e at the
published widths, with no chip: the chip's own compiler is installed
here and compiles for a described device (nothing runs, so these say
nothing of results or times). What interpret mode cannot show: a block
Mosaic refuses, more fast memory than a kernel may use.

All of them live in this one file, and the topology is described inside
a fixture: only the worker that is handed this file loads the TPU's
library."""

import jax
import jax.numpy as jnp
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


@pytest.mark.parametrize("bucket", [1, 64])
def test_fused_topk_compiles_over_the_heads_rows(one_chip, monkeypatch,
                                                 bucket):
    """19,072 rows of 4,096: the tile is cut to fit the fast memory
    (rank 64 keeps its 4,096-row tile)."""
    from predictionio_tpu.ops import fused_topk
    monkeypatch.setattr(fused_topk, "interpreted", lambda: False)
    assert fused_topk._tile_items(19072, 10, 4096) == (256, 256)
    assert fused_topk._tile_items(12_047_500, 10, 64) == (4096, 1024)
    call = fused_topk._pallas_topk(19072, 4096, k=10, bucket=bucket,
                                   banned_width=64, n_valid=19072)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(call).lower(
        sds((bucket, 4096), jnp.float32), sds((19072, 4096), jnp.float32),
        sds((bucket, 64), jnp.int32)).compile()
    assert _compiled_kernels(compiled) == 1
