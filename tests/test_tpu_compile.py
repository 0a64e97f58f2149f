"""Main-path Pallas kernels compile for a TPU v5e chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a described v5e topology, and refuses what the chip would refuse — a
block shape the tiling does not allow, too much fast memory, an op Mosaic
cannot lower. Interpret-mode tests cannot see any of that. Widths are the
published ones: qwen2-1.5b (12 query / 2 KV heads of 128, d=1536) and
rwkv6-7b (64 heads of 64).

The topology is described inside a module fixture, never at import time:
only the worker that runs this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention, flash_attention_forward
from repro.kernels.flash_decode import flash_decode_forward, paged_flash_decode_forward
from repro.kernels.rmsnorm import rmsnorm_forward
from repro.kernels.wkv6 import wkv6_forward

BF16 = jnp.bfloat16
HQ, HKV, D, DIM = 12, 2, 128, 1536  # qwen2-1.5b
SLOTS, PAGES, PAGE, TABLE = 8, 545, 16, 68  # 8 x 1088-token sequences


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 host, with the persistent compilation cache off
    (entries compiled for a described chip cannot be read back without
    one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"


@pytest.mark.parametrize("T", [2048, 1088])  # 1088: ragged last KV block
def test_dense_flash_decode_compiles(one_chip, T):
    _compile(lambda q, k, v, qp, kp: flash_decode_forward(q, k, v, qp, kp),
             one_chip, ((SLOTS, 1, HQ, D), BF16), ((SLOTS, HKV, T, D), BF16),
             ((SLOTS, HKV, T, D), BF16), ((SLOTS, 1), jnp.int32),
             ((SLOTS, T), jnp.int32))


@pytest.mark.parametrize("pool_dtype,queries", [
    (BF16, 1),  # the decode step
    (BF16, 256),  # a chunked-prefill window
    (jnp.int8, 1),  # int8 pool at page 16, with scale rows
])
def test_paged_flash_decode_compiles(one_chip, pool_dtype, queries):
    B = 1 if queries > 1 else SLOTS
    shapes = [((B, queries, HQ, D), BF16), ((PAGES, HKV, PAGE, D), pool_dtype),
              ((PAGES, HKV, PAGE, D), pool_dtype), ((PAGES, PAGE), jnp.int32),
              ((B, TABLE), jnp.int32), ((B, queries), jnp.int32)]
    if pool_dtype == jnp.int8:
        shapes.append(((PAGES, PAGE, 2), jnp.float32))
        fn = lambda q, k, v, pp, t, qp, sc: paged_flash_decode_forward(  # noqa: E731
            q, k, v, pp, t, qp, scale_pool=sc)
    else:
        fn = lambda q, k, v, pp, t, qp: paged_flash_decode_forward(  # noqa: E731
            q, k, v, pp, t, qp)
    _compile(fn, one_chip, *shapes)


QKV = [((4, 1024, HQ, D), BF16), ((4, 1024, HKV, D), BF16),
       ((4, 1024, HKV, D), BF16)]


def test_flash_attention_forward_compiles(one_chip):
    _compile(lambda q, k, v: flash_attention_forward(
        q, k, v, block_q=256, block_k=512), one_chip, *QKV)


def test_flash_attention_forward_backward_compiles(one_chip):
    def loss(q, k, v):
        out = flash_attention(q, k, v, block_q=256, block_k=512)
        return out.astype(jnp.float32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, *QKV)


@pytest.mark.parametrize("rows", [SLOTS, 4 * 1024])
def test_rmsnorm_compiles(one_chip, rows):
    _compile(lambda x, s: rmsnorm_forward(x, s), one_chip,
             ((rows, DIM), BF16), ((DIM,), BF16))


def test_wkv6_compiles(one_chip):
    H, K = 64, 64  # rwkv6-7b
    _compile(lambda r, k, v, w, u: wkv6_forward(r, k, v, w, u), one_chip,
             *[((1, 1024, H, K), BF16)] * 4, ((H, K), BF16))


def test_flash_attention_per_shard_compiles_on_four_chips(topo):
    """A Mosaic kernel cannot be partitioned by GSPMD: on a (4, 1) data
    mesh the training kernel must run per shard (the dispatcher wraps it
    in shard_map under the layer's partition), forward and backward."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.core.utils import set_mesh
    from repro.kernels.registry import per_shard

    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    batch = NamedSharding(mesh, PartitionSpec("data"))

    def loss(q, k, v):
        out = per_shard(lambda q, k, v: flash_attention(
            q, k, v, block_q=256, block_k=512),
            (("pod", "data"), None, "model", None), q, k, v)
        return out.astype(jnp.float32).sum()

    with set_mesh(mesh):
        _compile(jax.grad(loss, argnums=(0, 1, 2)), batch, *QKV)
