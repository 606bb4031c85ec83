#!/usr/bin/env python
"""Compile the device programs of `w2v300.block-rows`, and of row tables two
and four lane tiles wide, for a v5e that is described, not attached
(compile_rehearsal.py's method; that file is the record of PR 23 and is left
as it is).

Run by hand in the sandbox; nothing runs on a device, so every line it
prints is a compile, never a chip run:

    JAX_PLATFORMS=cpu python benchmark/tests/compile_rehearsal_wide.py

What it showed at PR 26: the scatter-add and the Pallas gather compile at
256, 384 and 512 lanes on 3,000,008 rows with 0 bytes of temporaries beside
a lane-wide delta (the tile view of the table is a bitcast on both sides of
the call; the table stays aliased), one 153.6 MB copy beside a 100,000 x 300
delta (the device holds it column-major); the table's row Get (XLA's
gather) and the whole-table checksum of `rows_table.final_checks` compile
beside the 4.6 GB table with 0 bytes of temporaries each."""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def attempt(name, fn, *shapes, **jit_kwargs):
    import jax
    t0 = time.perf_counter()
    try:
        compiled = jax.jit(fn, **jit_kwargs).lower(*shapes).compile()
    except Exception as e:  # the compiler's own words are the result
        print(json.dumps({"compile": name, "ok": False,
                          "error": str(e).strip().splitlines()[0][:400]}),
              flush=True)
        return False
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    print(json.dumps({
        "compile": name, "ok": True,
        "seconds": round(time.perf_counter() - t0, 1),
        "pallas_calls": text.count("tpu_custom_call"),
        "bitcasts": text.count(" bitcast("),
        "argument_bytes": mem.argument_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes}), flush=True)
    return True


def main():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from multiverso_tpu.ops import pallas_rows
    from multiverso_tpu.tables.matrix_table import _row_gather

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    def gather(t, i):
        return pallas_rows.gather_rows(t, i, interpret=False)

    def scatter(t, i, d):
        return pallas_rows.scatter_add_rows(t, i, d, interpret=False,
                                            sign=-1.0)

    # 3,000,000 rows and a sentinel, padded to whole tiles of 8 rows;
    # 100,000 ids an op in a bucket of 131,072
    rows, named, bucket = 3_000_008, 100_000, 131_072
    ok = True
    for lanes, width in ((256, 256), (384, 300), (384, 384), (512, 512)):
        what = f"{rows} x {lanes} float32"
        ok &= attempt(f"scatter_add_rows, {named} x {width} into {what}",
                      scatter, shape((rows, lanes)),
                      shape((bucket,), jnp.int32), shape((named, width)),
                      donate_argnums=(0,))
        ok &= attempt(f"gather_rows, {bucket} ids of {what}", gather,
                      shape((rows, lanes)), shape((bucket,), jnp.int32))
    ok &= attempt(f"_row_gather (XLA), {bucket} ids of {rows} x 384",
                  _row_gather, shape((rows, 384)),
                  shape((bucket,), jnp.int32))

    def column_sums(data):  # rows_table.final_checks, at the cell's size
        return jnp.sum(jnp.round(data[:3_000_000, :300] * 64)
                       .astype(jnp.int32), axis=0)

    ok &= attempt(f"whole-table checksum of {rows} x 384", column_sums,
                  shape((rows, 384)))
    print(json.dumps({"what": "compiles for a described v5e; no device ran "
                      "anything", "all_ok": bool(ok)}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
