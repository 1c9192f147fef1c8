"""The TPU's own compiler, without the chip: the device leg's executables at
the benchmark's widths (476,002 items × rank 128: 690 partitions in blocks of
4,096 rows, nprobe 26, serve_k 128) compile for a described v5e. What the
Pallas interpreter accepts, Mosaic can still refuse (tiling, scoped memory);
that has to fail here and not on the chip. Nothing runs, so nothing here is
a time or a result.

All such compiles live in THIS file, and the topology is described inside a
fixture: only the worker that is given this file loads the TPU's library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from incubator_predictionio_tpu.ops import retrieval

N_ITEMS, RANK, PARTITIONS, BLOCK, CENTROIDS = 476_002, 128, 690, 4096, 1024


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _rerank_args(one_chip, bucket, row_mask):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    blocks = (PARTITIONS, 1, BLOCK)
    return [
        s((bucket, CENTROIDS), jnp.float32), s((bucket, RANK), jnp.int8),
        s((bucket,), jnp.float32), s((bucket,), jnp.float32),
        s((), jnp.float32), s((PARTITIONS,), jnp.int32),
        s((PARTITIONS, BLOCK, RANK), jnp.int8), s(blocks, jnp.float32),
        s(blocks, jnp.float32), s(blocks, jnp.int32), s(blocks, jnp.float32),
        s((bucket, N_ITEMS), jnp.float32) if row_mask else None,
    ]


@pytest.mark.parametrize("bucket, row_mask", [(8, False), (64, False),
                                              (8, True)])
def test_two_stage_rerank_compiles_for_v5e(one_chip, bucket, row_mask):
    compiled = retrieval.two_stage_rerank.lower(
        *_rerank_args(one_chip, bucket, row_mask), nprobe=26, k=128).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "rerank_members_quantized" in text
    # the probed blocks are read by the kernel's pipeline: the program holds
    # no copy of the candidate rows (26 x 4096 x 128 B a query)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < bucket * 26 * BLOCK * RANK // 4


def test_quantize_user_rows_compiles_for_v5e(one_chip):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = retrieval.quantize_user_rows.lower(
        s((8,), jnp.int32), s((4_201_696, RANK), jnp.bfloat16),
        s((4_201_696,), jnp.float32)).compile()
    # a row gather, not a copy of the tower (the fused [U, 129] float32
    # layout made one: 10 ms a call on the chip)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
