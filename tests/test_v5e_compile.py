"""The row kernels compiled for a TPU v5e that is described, not attached:
what interpret mode cannot show (a slice not aligned to the tiling, too much
VMEM, a table copied where it should be aliased). Nothing runs on a device;
a pass here is a compile, never a chip run. The topology is described inside
a fixture, and only in this file: one process at a time may load the TPU's
library (docs: the on-chip-measurement guide, section 2)."""

import pytest

import jax
import jax.numpy as jnp

from multiverso_tpu.ops import pallas_rows


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes, **jit_kwargs):
    specs = [jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
             for dims, dtype in shapes]
    return jax.jit(fn, **jit_kwargs).lower(*specs).compile()


# rows (a sentinel past the table, whole tiles of 8 where the table is wide),
# lanes, delta columns: the benchmark's two table shapes and their neighbours
@pytest.mark.parametrize("rows,lanes,width", [
    (10_000_001, 128, 128), (1_000_001, 128, 50), (3_000_008, 256, 256),
    (3_000_008, 384, 300), (3_000_008, 512, 512)])
def test_scatter_add_compiles_in_place_at_every_width(one_chip, rows, lanes,
                                                      width):
    """100,000 delta rows in a 131,072-slot id bucket: Mosaic takes the
    kernel, the table is aliased whole (the tile view of a wide table is a
    bitcast, not a copy), and the only temporary is the row-major copy of a
    delta narrower than the lanes."""
    def scatter(table, ids, deltas):
        return pallas_rows.scatter_add_rows(table, ids, deltas,
                                            interpret=False, sign=-1.0)

    compiled = _compile(scatter, one_chip, ((rows, lanes), jnp.float32),
                        ((131_072,), jnp.int32),
                        ((100_000, width), jnp.float32),
                        donate_argnums=(0,))
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert text.count("tpu_custom_call") == 1
    assert mem.alias_size_in_bytes >= rows * lanes * 4
    copy_of_delta = 100_000 * lanes * 4 if width % 128 else 0
    assert mem.temp_size_in_bytes <= copy_of_delta + (1 << 20)


@pytest.mark.parametrize("lanes", [128, 384])
def test_gather_rows_compiles(one_chip, lanes):
    def gather(table, ids):
        return pallas_rows.gather_rows(table, ids, interpret=False)

    rows = 3_000_008
    compiled = _compile(gather, one_chip, ((rows, lanes), jnp.float32),
                        ((131_072,), jnp.int32))
    assert compiled.as_text().count("tpu_custom_call") == 1
    # never a copy of the table: at most the result, once more in row order
    assert (compiled.memory_analysis().temp_size_in_bytes
            <= 131_072 * lanes * 4 + (1 << 20))
