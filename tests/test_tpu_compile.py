"""The graph path's Pallas kernels compile for a TPU v5e (Mosaic lowering,
``interpret=False``) at the widths chip_smoke.py produces on one chip.

Nothing here runs a kernel: each test lowers and compiles for one chip of
a described ``v5e:2x2`` topology, which needs the TPU compiler but no TPU.
The topology is described inside a fixture, so collecting this file never
loads the TPU library; where it cannot be described, the tests skip.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import csr_spmv as cs
from repro.kernels import varint as vk

# chip_smoke.py on one chip (seed 0, RMAT scale 21, P=8): the largest
# padded widths of its OOC store's device decode (DeviceChunkDecoder
# .max_widths, power-of-two buckets) ...
DECODE_EDGES = 1_048_576        # edges per chunk
DECODE_PAIR_BYTES = 262_144     # DCSR-delta pair-stream bytes per chunk
DECODE_RESIDUE_BYTES = 2_097_152  # dst-residue bytes per chunk
DECODE_RUNS = 131_072           # DCSR runs (sources) per chunk
# ... and its block_csr phase's LOCAL tiles (RMAT scale 14, P=8), per
# destination partition: tile slots, kernel grid rows x tiles per row,
# and source column blocks.
TILE = 8
TILE_SLOTS = 21_486
TILE_ROWS = 548
TILES_PER_ROW = 1_344
COL_BLOCKS = 8 * TILE_ROWS
QUERIES = 4
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [None if s is None else
            jax.ShapeDtypeStruct(s[0], s[1], sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES
    return compiled


def _combine_shapes(mode, vec_shape):
    tile = ((TILE_SLOTS, TILE, TILE), jnp.float32)
    ints = lambda n: ((n,), jnp.int32)
    return (ints(TILE_ROWS + 1), ints(TILE_SLOTS), ints(TILE_SLOTS),
            ints(TILE_ROWS), None if mode == "min" else tile,
            None if mode == "add" else tile, tile,
            (vec_shape, jnp.float32), (vec_shape, jnp.float32))


@pytest.mark.parametrize("mode", ["add", "add_b", "min"])
def test_block_csr_combine_compiles(one_chip, mode):
    def fn(rp, idx, col, cnt, tv, tb, tc, xv, xc):
        return cs.block_csr_combine(
            rp, idx, col, cnt, tv, tb, tc, xv, xc, mode=mode, tile=TILE,
            max_tiles_per_row=TILES_PER_ROW, interpret=False)
    _compile(one_chip, fn, *_combine_shapes(mode, (COL_BLOCKS * TILE,)))


@pytest.mark.parametrize("mode", ["add", "min"])
def test_block_csr_combine_mq_compiles(one_chip, mode):
    def fn(rp, idx, col, cnt, tv, tb, tc, xv, xc):
        return cs.block_csr_combine_mq(
            rp, idx, col, cnt, tv, tb, tc, xv, xc, mode=mode, tile=TILE,
            max_tiles_per_row=TILES_PER_ROW, num_queries=QUERIES,
            interpret=False)
    _compile(one_chip, fn,
             *_combine_shapes(mode, (COL_BLOCKS * TILE, QUERIES)))


@pytest.mark.parametrize("mode", ["add", "max"])
def test_blocked_scan_compiles(one_chip, mode):
    _compile(one_chip,
             lambda x: vk.blocked_scan(x, mode=mode, interpret=False),
             ((DECODE_EDGES,), jnp.int32))


@pytest.mark.parametrize("nbytes,count", [
    (DECODE_RESIDUE_BYTES, DECODE_EDGES),
    (DECODE_PAIR_BYTES, 2 * DECODE_RUNS),
], ids=["residues", "pairs"])
def test_varint_decode_compiles(one_chip, nbytes, count):
    _compile(one_chip,
             lambda b, n: vk.varint_decode(b, n, count=count,
                                           interpret=False),
             ((nbytes,), jnp.uint8), ((), jnp.int32))
