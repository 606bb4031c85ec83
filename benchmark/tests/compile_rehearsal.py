#!/usr/bin/env python
"""Compile the device programs of the benchmark's cells, and of the word2vec
cells it could not take, for a v5e that is described, not attached.

Run by hand in the sandbox; nothing runs on a device, so every line it
prints is a compile, never a chip run:

    JAX_PLATFORMS=cpu python benchmark/tests/compile_rehearsal.py

What it showed at PR 23 (PERF.md, Findings): the row kernels compile at 128
lanes for 131,072 ids on the 10,000,001-row float32 table and not on a
bfloat16 one (the table of the control); 1,048,576 ids need 4 MiB of the
1 MiB of SMEM; at 256, 384 or 512 lanes neither row kernel compiles
(a one-row slice of an (8,128)-tiled table), so the fused word2vec
transaction at 793,471 x 300 cannot run on one chip; on four chips, with
XLA's scatter on row-sharded tables, it compiles.
"""

import collections
import json
import os
import re
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
        return
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    print(json.dumps({
        "compile": name, "ok": True,
        "seconds": round(time.perf_counter() - t0, 1),
        "pallas_calls": text.count("tpu_custom_call"),
        "collectives": dict(collections.Counter(re.findall(
            r"(all-reduce|all-gather|all-to-all|collective-permute|"
            r"reduce-scatter)\(", text))),
        "argument_bytes_per_device": mem.argument_size_in_bytes,
        "temp_bytes_per_device": mem.temp_size_in_bytes}), flush=True)


def main():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                              SingleDeviceSharding)

    from multiverso_tpu.models.vocab import Dictionary
    from multiverso_tpu.models.word2vec import (Word2VecConfig,
                                                make_block_train_step)
    from multiverso_tpu.ops import pallas_rows

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    mesh = Mesh(np.array(topo.devices).reshape(4), ("server",))
    by_rows = NamedSharding(mesh, PartitionSpec("server", None))
    everywhere = NamedSharding(mesh, PartitionSpec())

    def shape(dims, dtype, sharding=chip):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    def gather(t, i):
        return pallas_rows.gather_rows(t, i, interpret=False)

    def scatter(t, i, d):
        return pallas_rows.scatter_add_rows(t, i, d, interpret=False)

    # -- the row cells: 100,000 ids an op -> a bucket of 131,072
    rows = 10_000_001
    # (the bfloat16 line is the table of the cells' control)
    for lanes, ids, dtype in ((128, 131_072, jnp.float32),
                              (128, 131_072, jnp.bfloat16),
                              (128, 1_048_576, jnp.float32),
                              (384, 1_024, jnp.float32)):
        what = f"{ids} ids of {rows} x {lanes} {jnp.dtype(dtype).name}"
        attempt(f"gather_rows, {what}", gather,
                shape((rows, lanes), dtype), shape((ids,), jnp.int32))
        attempt(f"scatter_add_rows, {what}", scatter,
                shape((rows, lanes), dtype), shape((ids,), jnp.int32),
                shape((ids, lanes), dtype), donate_argnums=(0,))

    # -- the fused word2vec transaction, rebuilt as PSTrainer._build_txn_fn
    # builds it (the trainer itself places its tables on jax.devices(),
    # which is the CPU here): gather both tables' candidate rows, run the
    # block kernel over the chunked submission, apply both deltas. Buckets
    # are those a 524,288-token submission reaches at sample=1e-3.
    vocab, dim, lanes = 793_471, 300, 384
    b_in, b_out, n_chunks, chunk, pool = 131_072, 262_144, 64, 8_192, 16_384
    config = Word2VecConfig(vocab_size=vocab, dim=dim, window=5, negatives=5,
                            batch_pairs=32_768, sample=1e-3, neg_sharing=1)
    d = Dictionary()
    d.counts = np.maximum((1e7 / np.arange(1, vocab + 1)).astype(np.int64), 5)
    raw = make_block_train_step(config, d, jit=False, neg_table=True)

    def txn(apply_rows):
        def fn(data_in, data_out, packed, key, lr):
            ids_in = packed[:b_in]
            ids_out = packed[b_in:b_in + b_out]
            o = b_in + b_out
            blocks_c = packed[o:o + n_chunks * chunk].reshape(
                (n_chunks, chunk))
            slot_alias = packed[o + n_chunks * chunk:]
            w_in = data_in[ids_in][:, :dim]
            w_out = data_out[ids_out][:, :dim]

            def body(carry, blk):
                params, key = carry
                key, sub = jax.random.split(key)
                params, loss, pairs = raw(params, sub, blk, lr, slot_alias,
                                          with_pairs=True)
                return (params, key), (loss, pairs)

            (params, _), (losses, pairs) = jax.lax.scan(
                body, ({"w_in": w_in, "w_out": w_out}, key), blocks_c)
            pad = ((0, 0), (0, lanes - dim))
            d_in = jnp.pad(params["w_in"] - w_in, pad)
            d_out = jnp.pad(params["w_out"] - w_out, pad)
            return (apply_rows(data_in, ids_in, d_in),
                    apply_rows(data_out, ids_out, d_out),
                    (losses * pairs).sum() / pairs.sum())
        return fn

    packed = b_in + b_out + n_chunks * chunk + pool
    attempt("word2vec fused transaction, one chip, Pallas scatter, "
            f"2 x {vocab + 1} x {lanes}", txn(scatter),
            shape((vocab + 1, lanes), jnp.float32),
            shape((vocab + 1, lanes), jnp.float32),
            shape((packed,), jnp.int32), shape((2,), jnp.uint32),
            shape((), jnp.float32), donate_argnums=(0, 1))
    attempt("word2vec fused transaction, four chips, XLA scatter on "
            f"row-sharded 2 x {vocab + 1} x {lanes}",
            txn(lambda t, i, dl: t.at[i].add(dl)),
            shape((vocab + 1, lanes), jnp.float32, by_rows),
            shape((vocab + 1, lanes), jnp.float32, by_rows),
            shape((packed,), jnp.int32, everywhere),
            shape((2,), jnp.uint32, everywhere),
            shape((), jnp.float32, everywhere), donate_argnums=(0, 1))
    print(json.dumps({"what": "compiles for a described v5e; no device ran "
                      "anything"}))


if __name__ == "__main__":
    main()
