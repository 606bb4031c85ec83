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
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """The table mesh of a four-chip host: one axis, `server`."""
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.array(topo.devices[:4]), ("server",))


def _compile(fn, one_chip, *shapes, **jit_kwargs):
    specs = [jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
             for dims, dtype in shapes]
    return jax.jit(fn, **jit_kwargs).lower(*specs).compile()


# rows (a sentinel past the table, whole tiles of 8 where the table is wide),
# lanes, delta columns: the benchmark's two table shapes and their neighbours
@pytest.mark.parametrize("rows,lanes,width", [
    (10_000_001, 128, 128), (1_000_001, 128, 50), (3_000_008, 256, 256),
    (3_000_008, 384, 300), (3_000_008, 512, 512), (200_008, 4096, 4096)])
def test_scatter_add_compiles_in_place_at_every_width(one_chip, rows, lanes,
                                                      width):
    """100,000 delta rows in a 131,072-slot id bucket: Mosaic takes the
    kernel at the row group kept (its one wait a group on a descriptor of
    the whole scratch block, VMEM to VMEM), up to the widest table the VMEM
    budget admits (4,096 lanes); the table is aliased whole (the tile view
    of a wide table is a bitcast, not a copy), and the only temporary is
    the row-major copy of a delta narrower than the lanes."""
    import re

    from benchmark import common

    assert pallas_rows.fits_vmem(lanes, 4)
    assert lanes < 4096 or not pallas_rows.fits_vmem(lanes + 128, 4)

    def scatter(table, ids, deltas):
        return pallas_rows.scatter_add_rows(table, ids, deltas,
                                            interpret=False, sign=-1.0)

    compiled = _compile(scatter, one_chip, ((rows, lanes), jnp.float32),
                        ((131_072,), jnp.int32),
                        ((100_000, width), jnp.float32),
                        donate_argnums=(0,))
    text, mem = _hlo_text(compiled), compiled.memory_analysis()
    # ONE custom call a launch, under the name and with the operands the
    # benchmark's readers match: the id bucket, the delta, the table
    kernels = [line for line in text[text.index("ENTRY"):].splitlines()
               if "tpu_custom_call" in line]
    assert len(kernels) == 1, kernels
    assert re.match(r"\s*(ROOT )?%_scatter_add_call", kernels[0]), kernels
    shapes = common.load_module("layers", "row_scatter_roofline").SHAPES
    assert shapes.search(kernels[0]).groups() == ("131072", "100000",
                                                  str(width)), kernels
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


@pytest.mark.parametrize("rows,lanes", [(10_000_001, 128), (3_000_008, 384)])
def test_row_get_gathers_the_ids_named_not_the_bucket(one_chip, rows, lanes):
    """The table's row Get of 100,000 ids in their 131,072-slot bucket, at
    the benchmark's two table shapes, its ids the bucket-long array an Add
    takes too and the slots it gathers a static slice of them (`live`,
    PR 39): the result is the bucket; exactly one
    fusion gathers, over `%data` and an s32 id array (how
    `benchmark/row_bytes.py` finds it in a trace), of at least the ids
    named and under one step more; the slice adds no pass over the ids
    (XLA folds it into the one that was there) and no copy; the fill pass
    is not such a fusion; the
    only temporary is the gathered rows (no second copy of the result);
    and the compiler tiles that gather's rows by 256, the form the chip ran
    2.4 times as fast as the 128 it picks for a whole number of id tiles
    (PERF.md, Findings, PR 27)."""
    import re

    from benchmark.row_bytes import GATHER_EVENT
    from multiverso_tpu.tables.matrix_table import (_live_slots,
                                                    _row_gather_jit)

    named, bucket = 100_000, 131_072
    live = _live_slots(named, bucket)
    assert named <= live < named + bucket // 32
    # the table's own jit: the module and its parameters keep the names a
    # trace is read by
    compiled = _row_gather_jit.lower(
        jax.ShapeDtypeStruct((rows, lanes), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((bucket,), jnp.int32, sharding=one_chip),
        bucket=bucket, sentinel=rows - 8, live=live).compile()
    # the text as a trace names its events: operands with their shapes
    from jax._src.lib import xla_client
    options = xla_client._xla.HloPrintOptions()
    options.print_operand_shape = True
    options.print_percent = True
    text = compiled.runtime_executable().hlo_modules()[0].to_string(options)
    assert text.startswith("HloModule jit__row_gather")
    assert f"->f32[{bucket},{lanes}]" in text.splitlines()[0]
    entry = text[text.index("ENTRY"):]
    gathers = [line for line in entry.splitlines()
               if GATHER_EVENT.search(line)]
    assert len(gathers) == 1, gathers
    assert int(GATHER_EVENT.search(gathers[0]).group(1)) == live
    assert "kind=kCustom" in gathers[0]
    assert re.search(r'"integer_config":\{"integer":"256"\}', gathers[0])
    # the whole bucket of ids is read by one fusion alone, the pass that
    # wraps negative ids, which now also cuts them to the slots gathered
    over_ids = [line for line in entry.splitlines()
                if f"s32[{bucket}]" in line and " fusion(" in line]
    assert len(over_ids) == 1 and f"= s32[{live}]" in over_ids[0], over_ids
    assert " slice(" not in entry and " copy(" not in entry
    assert (compiled.memory_analysis().temp_size_in_bytes
            <= live * lanes * 4 + (1 << 20))


def _hlo_text(compiled):
    """The compiled module as a trace names its events: operands with their
    shapes."""
    from jax._src.lib import xla_client
    options = xla_client._xla.HloPrintOptions()
    options.print_operand_shape = True
    options.print_percent = True
    return compiled.runtime_executable().hlo_modules()[0].to_string(options)


# table rows (whole tiles of 8 on every shard where the table is wide),
# lanes, delta columns: the four-chip cell's table and the wide one's shape
SHARDED_TABLES = [(40_000_004, 128, 128), (3_000_032, 384, 300)]


@pytest.mark.parametrize("rows,lanes,width", SHARDED_TABLES)
def test_sharded_row_add_compiles_with_the_kernel_on_every_shard(
        four_chips, rows, lanes, width):
    """A device Add of 100,000 rows into a table row-sharded over a v5e
    2x2, at `emb128x4.bulk-rows`' shapes: the ids come in the routed ops'
    one form, the 102,408 slots a Get of them gathers
    (`RowPlan.launch_ids`), the 100,000 the delta has rows for are sorted on
    the chip (one sort, of the ids named and not of the longer array),
    Mosaic takes the kernel with its live count on a shard's block, named
    `shard_scatter` (how `benchmark/shard_trace.py` finds it in a trace),
    the table's blocks are aliased, the delta's rows leave the first chip
    in three collective-permutes of one segment each, pairs (0, s), the
    segments' ids and counts beside them, and no all-reduce, all-gather or
    all-to-all carries anything."""
    import re

    from multiverso_tpu.ops import sharded_rows

    from multiverso_tpu.tables.matrix_table import _live_slots

    programs = sharded_rows.ShardedRows(four_chips, False, -1.0)
    named, shards = 100_000, 4
    slots = _live_slots(named, 131_072)
    assert slots == 102_408
    capacity = sharded_rows.shard_capacity(25_137, named, shards)
    assert shards * capacity <= 1.15 * named and capacity % 1024

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=programs.by_rows)

    compiled = programs.add.lower(
        spec((rows, lanes), jnp.float32), spec((shards * slots,), jnp.int32),
        spec((shards * named, width), jnp.float32),
        capacity=capacity).compile()
    text, mem = _hlo_text(compiled), compiled.memory_analysis()
    entry = text[text.index("ENTRY"):]
    assert text.startswith("HloModule jit_sharded_row_add")
    kernels = [line for line in entry.splitlines()
               if "tpu_custom_call" in line]
    assert len(kernels) == 1 and re.match(r"\s*(ROOT )?%shard_scatter",
                                          kernels[0]), kernels
    # the counted kernel's operands, in the order `benchmark/shard_trace.py`
    # documents: a segment's ids, its live count, its delta, the block
    assert re.search(
        rf"custom-call\(s32\[{capacity}\]\S* %\S+, s32\[1\]\S* %\S+, "
        rf"f32\[{capacity},{width}\]\S* %\S+, f32\[", kernels[0]), kernels
    assert mem.alias_size_in_bytes >= rows // shards * lanes * 4
    sends = re.findall(r"collective-permute-start\(f32\[(\d+),(\d+)\].*?"
                       r"source_target_pairs=\{\{0,(\d)\}\}", entry)
    assert sorted(sends) == [(str(capacity), str(width), str(s))
                             for s in (1, 2, 3)], sends
    metas = re.findall(r"collective-permute-start\(s32\[(\d+)\].*?"
                       r"source_target_pairs=\{\{0,(\d)\}\}", entry)
    assert sorted(metas) == [(str(capacity + 1), str(s)) for s in (1, 2, 3)]
    sorts = [line for line in entry.splitlines() if " sort(" in line]
    assert len(sorts) == 1 and f"s32[{named}]" in sorts[0], sorts
    assert f"s32[{slots}]" not in sorts[0]
    for collective in ("all-reduce", "all-gather", "all-to-all"):
        assert collective not in entry
    # beside the table: the pieces on their way, never the bucket (8.05
    # segments at 384 lanes and a group of 256, 7.90 at a group of 64; the
    # 131,072-slot bucket would be 5.07 segments a piece)
    assert mem.temp_size_in_bytes <= 8.5 * capacity * lanes * 4


def test_sharded_row_get_compiles_and_sends_the_rows_asked(four_chips):
    """A Get of 100,000 ids from the sharded table: the first chip sends
    each shard its segment's ids, each shard gathers them from its block in
    the fast form (rows tiled by 256, PR 27), the three other shards send
    their rows to the first chip, pairs (s, 0), the result is the
    131,072-slot bucket on every shard (the first's is the answer), and
    nothing is reduced or gathered over the mesh."""
    import re

    from benchmark.shard_trace import GATHER
    from multiverso_tpu.ops import sharded_rows
    from multiverso_tpu.tables.matrix_table import _live_slots

    programs = sharded_rows.ShardedRows(four_chips, False, -1.0)
    rows, lanes, _ = SHARDED_TABLES[0]  # XLA's gather: no kernel to widen
    named, bucket, shards = 100_000, 131_072, 4
    live = _live_slots(named, bucket)
    capacity = sharded_rows.shard_capacity(25_137, live, shards)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=programs.by_rows)

    compiled = programs._get.lower(
        spec((rows, lanes), jnp.float32), spec((shards * live,), jnp.int32),
        capacity=capacity, bucket=bucket).compile()
    text = _hlo_text(compiled)
    assert text.startswith("HloModule jit_sharded_row_get")
    assert f"->f32[{bucket},{lanes}]" in text.splitlines()[0]
    entry = text[text.index("ENTRY"):]
    gathers = [(line, GATHER.search(line)) for line in entry.splitlines()
               if GATHER.search(line)]
    over_block = [line for line, m in gathers
                  if int(m.group(3)) == rows // shards]
    assert len(over_block) == 1 and len(gathers) == 2, gathers
    assert int(GATHER.search(over_block[0]).group(1)) == capacity
    assert re.search(r'"integer_config":\{"integer":"256"\}', over_block[0])
    sends = re.findall(r"collective-permute-start\(f32\[(\d+),(\d+)\].*?"
                       r"source_target_pairs=\{\{(\d),0\}\}", entry)
    assert sorted(sends) == [(str(capacity), str(lanes), str(s))
                             for s in (1, 2, 3)], sends
    for collective in ("all-reduce", "all-gather", "all-to-all"):
        assert collective not in entry


# table rows (a sentinel past the table), lanes, gradient columns: the
# `emb128rws.bulk-updates` cell's table, and a width under one lane tile
@pytest.mark.parametrize("rows,lanes,width", [(10_000_001, 128, 128),
                                              (1_000_001, 128, 50)])
def test_row_state_add_compiles_with_the_row_kernel(one_chip, rows, lanes,
                                                    width):
    """A device Add of 100,000 gradient rows under `rowwise_adagrad`, the
    table's own program: ONE module, `jit__row_state_add` (how
    `benchmark/rws_trace.py` finds it in a trace), whose table rows move
    through the Pallas row kernel, named `_scatter_add_call` like the plain
    Add's (how `row_scatter_roofline`'s reader finds it) with the id bucket
    and the scaled gradient as its first operands; table and state are
    aliased in place; the state is one float32 a row, lane-dense (no
    `[rows, 1]` array tiled to 128 lanes anywhere) and gathered as rows of
    128, and nothing of the table's size is a temporary. Fails if the updater leaves the kernel."""
    import functools
    import re

    import numpy as np

    from benchmark import common
    from multiverso_tpu.tables.matrix_table import _make_row_state_add
    from multiverso_tpu.updaters import get_updater

    row_scatter_roofline = common.load_module("layers",
                                              "row_scatter_roofline")
    named, bucket = 100_000, 131_072
    updater = get_updater(np.float32, "rowwise_adagrad")
    scatter = functools.partial(pallas_rows.scatter_add_rows,
                                interpret=False, sign=1.0)
    program = _make_row_state_add(updater, scatter, width)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state_rows = -(-rows // 1024) * 1024  # as the table pads it
    compiled = program.lower(
        spec((rows, lanes), jnp.float32),
        {"s": spec((state_rows,), jnp.float32)},
        spec((bucket,), jnp.int32), spec((named, width), jnp.float32),
        spec((), jnp.int32), spec((4,), jnp.float32)).compile()
    text, mem = _hlo_text(compiled), compiled.memory_analysis()
    assert text.startswith("HloModule jit__row_state_add")
    entry = text[text.index("ENTRY"):]
    kernels = [line for line in entry.splitlines()
               if "tpu_custom_call" in line]
    assert len(kernels) == 1, kernels
    assert re.match(r"\s*(ROOT )?%_scatter_add_call", kernels[0]), kernels
    shapes = row_scatter_roofline.SHAPES.search(kernels[0])
    assert shapes and shapes.groups() == (str(bucket), str(named),
                                          str(width)), kernels
    assert mem.alias_size_in_bytes >= rows * lanes * 4 + state_rows * 4
    assert f"f32[{state_rows},1]" not in text
    # the state is read as rows of 128 floats (XLA's row gather), not as
    # single floats: 0.137 ms against 1.195 on the chip (PERF.md, PR 33)
    assert re.search(rf"f32\[{named},128\]\S* fusion\(f32\[{state_rows // 128}"
                     r",128\]", entry), "no row gather over the state"
    # the scaled gradient, its row-major copy where it is narrower than
    # the lanes, and the state in fast memory: never a table
    assert mem.temp_size_in_bytes <= 3 * named * lanes * 4 + rows * 4 * 2


# the table group's slab at the cell `dlrm26.step-rows`: the 26 members'
# 19,063,992 rows and the sentinel, 2.44e9 elements in ONE array (the first
# of more than 2^31 on a chip), and a step's 102,900 rows
SLAB_ROWS, STEP_ROWS = 19_063_992 + 1, 102_900


@pytest.mark.parametrize("program", ["add", "add of a held delta", "get",
                                     "piece"])
def test_group_slab_programs_compile_at_19_million_rows(one_chip, program):
    """A group op is the matrix table's own programs over the slab: the Add
    is ONE custom call of the row kernel (absent: a failure) that aliases
    the 9.76 GB slab whole, under a delta of the step's rows and under one
    held at the Get's bucket with the count of ids in the last id slot (the
    cell's: the same operands, so the same event to a trace's readers, and
    nothing in front of the kernel); the Get one gather fusion over `%data` of the
    slots `_live_slots` gives out of the bucket of ids, its result the bucket; a piece of the slab
    on its way up is written in place (`mesh._set_rows`). None makes a
    temporary of the slab's size."""
    from benchmark.row_bytes import GATHER_EVENT
    from multiverso_tpu.parallel import mesh as mesh_lib
    from multiverso_tpu.tables.matrix_table import (_live_slots,
                                                    _row_gather_jit)

    slab = jax.ShapeDtypeStruct((SLAB_ROWS, 128), jnp.float32,
                                sharding=one_chip)
    bucket, slab_bytes = 131_072, SLAB_ROWS * 128 * 4
    if program.startswith("add"):
        from benchmark.layers import row_scatter_roofline

        held = program != "add"
        compiled = _compile(
            lambda table, ids, deltas: pallas_rows.scatter_add_rows(
                table, ids, deltas, interpret=False, tail_count=held),
            one_chip, ((SLAB_ROWS, 128), jnp.float32),
            ((bucket,), jnp.int32),
            ((bucket if held else STEP_ROWS, 128), jnp.float32),
            donate_argnums=(0,))
        text = _hlo_text(compiled)
        entry = text[text.index("ENTRY"):].splitlines()
        kernels = [line for line in entry if "tpu_custom_call" in line]
        assert len(kernels) == 1 and "_scatter_add_call" in kernels[0]
        # the standing readers find the launch's shapes in the event's name
        slots, rows, lanes = map(int, row_scatter_roofline.SHAPES.search(
            kernels[0]).groups())
        assert (slots, rows, lanes) == (bucket,
                                        bucket if held else STEP_ROWS, 128)
        assert not [line for line in entry if " fusion(" in line]
        assert compiled.memory_analysis().alias_size_in_bytes >= slab_bytes
    elif program == "get":
        live = _live_slots(STEP_ROWS, bucket)
        compiled = _row_gather_jit.lower(
            slab, jax.ShapeDtypeStruct((bucket,), jnp.int32,
                                       sharding=one_chip),
            bucket=bucket, sentinel=SLAB_ROWS - 1, live=live).compile()
        text = _hlo_text(compiled)
        assert f"->f32[{bucket},128]" in text.splitlines()[0]
        gathers = [line for line in text[text.index("ENTRY"):].splitlines()
                   if GATHER_EVENT.search(line)]
        assert len(gathers) == 1, gathers
        assert int(GATHER_EVENT.search(gathers[0]).group(1)) == live
    else:
        compiled = mesh_lib._set_rows.lower(
            slab, jax.ShapeDtypeStruct((524_288, 128), jnp.float32,
                                       sharding=one_chip),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
        assert "dynamic-update-slice" in _hlo_text(compiled)
        assert compiled.memory_analysis().alias_size_in_bytes >= slab_bytes
    assert compiled.memory_analysis().temp_size_in_bytes <= (
        bucket * 128 * 4 + (1 << 20))


def test_a_served_keyed_ftrl_add_compiles_at_its_gradients_length(one_chip):
    """The keyed FTRL Add of the served cell (`ftrlctr8.remote-steps`:
    20,100-20,600 keys of a 2,048-sample minibatch in a bucket of 32,768):
    the keys go up at the bucket and a host gradient at the slots the
    program works on (PR 50), so the program's shape follows the bucket
    and those slots alone; the row kernel still serves it."""
    from multiverso_tpu.tables import ftrl_table as ft
    from multiverso_tpu.tables.device_ids import live_slots

    size, bucket = 882_774_573, 32_768
    padded = -(-(size + 1) // 1024) * 1024
    live = live_slots(20_370, bucket)
    assert live == live_slots(19_500, bucket) == 20_488
    _, add = ft._make_programs(0.1, 1.0, 1.0, 1.0, size)
    state = jax.ShapeDtypeStruct((padded,), jnp.float32, sharding=one_chip)
    compiled = add.lower(
        state, state,
        jax.ShapeDtypeStruct((bucket,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((live,), jnp.float32, sharding=one_chip),
        live=live, rows=False).compile()
    text, mem = _hlo_text(compiled), compiled.memory_analysis()
    assert text.splitlines()[0].startswith("HloModule jit__ftrl_keyed_add")
    entry = text[text.index("ENTRY"):].splitlines()
    assert len([line for line in entry if "tpu_custom_call" in line]) == 1
    assert mem.alias_size_in_bytes >= 2 * 4 * padded


def test_keyed_ftrl_add_compiles_with_the_row_kernel_in_place(one_chip):
    """The keyed FTRL Add of the benchmark's cell (882,774,573 keys, 111,300
    named in a 131,072 bucket) on the path one chip takes (PR 42): ONE
    program under the name a trace is read by, ONE custom call of the row
    kernel and no scatter into a state, `z` and `n` (3.53 GB each) aliased
    whole. Since PR 49 the kernel works the FTRL step out on the rows it
    reads: the program gathers NO state (nothing but the custom call and
    the bitcasts around it touches `z` or `n`, as a flat array or as rows
    of 128), and it writes no block a slot for the kernel: the keys and
    the gradient go in lane-dense, so the temporaries are the sort's (a
    few arrays of the slots, under 8 MB where the three blocks a slot were
    177). Since PR 51 the kernel walks the keys' distinct rows, compacted
    in this program (a second sort, a cumulative sum, two comparisons of
    1,024 chunks with 1,024 groups: still no scatter into a state and under
    8 MB), and the program's third result is their count. The kernel
    refuses more keys than its scalar prefetch holds (the table keeps XLA's
    program there: `RowPlan.largest_bucket`)."""
    from multiverso_tpu.tables import ftrl_table as ft
    from multiverso_tpu.tables.device_ids import live_slots

    size, bucket = 882_774_573, 131_072
    padded = -(-(size + 1) // 1024) * 1024
    live = live_slots(111_300, bucket)
    _, add = ft._make_programs(0.1, 1.0, 1.0, 1.0, size)
    state = jax.ShapeDtypeStruct((padded,), jnp.float32, sharding=one_chip)
    compiled = add.lower(
        state, state,
        jax.ShapeDtypeStruct((bucket,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((bucket,), jnp.float32, sharding=one_chip),
        live=live, rows=False).compile()
    text, mem = _hlo_text(compiled), compiled.memory_analysis()
    assert text.splitlines()[0].startswith("HloModule jit__ftrl_keyed_add")
    entry = text[text.index("ENTRY"):].splitlines()
    assert len([line for line in entry if "tpu_custom_call" in line]) == 1
    # the only scatter left sums a repeated key's gradients, over the slots
    assert "scatter(f32[%d]" % padded not in text
    assert "scatter(f32[%d]" % live in text
    # no gather of a state: a state, flat or as rows of 128, is seen by no
    # computation but the entry, and there by the custom call alone
    flat, rows = "f32[%d]" % padded, "f32[%d,128]" % (padded // 128)
    fused = "\n".join(text[:text.index("ENTRY")].splitlines()[1:])
    assert flat not in fused and rows not in fused
    touching = [line for line in entry[1:] if flat in line or rows in line]
    assert touching and all(
        any(" %s(" % op in line for op in (
            "parameter", "bitcast", "custom-call", "get-tuple-element",
            "tuple")) for line in touching), touching
    assert not [line for line in text.splitlines()
                if " gather(" in line and "slice_sizes={1,128}" in line]
    assert mem.alias_size_in_bytes >= 2 * 4 * padded
    assert mem.temp_size_in_bytes <= 8 << 20
    # the third result: the rows the kernel walked, a scalar (PR 51)
    assert [(o.shape, str(o.dtype)) for o in jax.tree.leaves(
        compiled.out_info)] == [((padded,), "float32")] * 2 + [((), "int32")]
    # the cell's bucket is the largest the kernel's scalar prefetch holds
    assert bucket == pallas_rows.PREFETCH_SLOTS
    with pytest.raises(ValueError, match="add_at_lanes"):
        jax.eval_shape(
            lambda s, k: pallas_rows.add_at_lanes(
                (s,), k, (k.astype(jnp.float32),), k >= 0, interpret=False),
            state, jax.ShapeDtypeStruct((bucket + 1,), jnp.int32))
