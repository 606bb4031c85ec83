"""What the row-op drivers share: sizes, the table made from --seed, the
pools of pooled Adds, and the checks after the window (a seeded sample of
rows against the reference, and a whole-table checksum on the device)."""

import json

import numpy as np

from benchmark import common

# flags are sticky across mv.shutdown(): every init names all it relies on
# (chip_smoke.py's list)
INIT_FLAGS = dict(sync=False, ssp_staleness=-1, deterministic=False,
                  ma=False, ps_role="default", updater_type="default",
                  local_workers=1, mesh_axes="server")


def sizes(run):
    """(table shape dict, traffic parameters), shrunk in a rehearsal."""
    table = dict(run.config["table"])
    params = {k: v for k, v in run.traffic.items() if k != "rehearse"}
    if run.rehearse:
        small = dict(run.traffic.get("rehearse", {}))
        table["num_row"] = small.pop("num_row", table["num_row"])
        params.update(small)
    return table, params


def start_table(run, table, remote_workers):
    """Start the program on the cell's chips and make the table from the
    seed. Returns (worker table, reference module, initial column sums)."""
    import multiverso_tpu as mv

    ref = common.load_module("reference", run.cell["config"])
    mv.init(mesh_shape=str(run.chips), remote_workers=remote_workers,
            **INIT_FLAGS)
    run.phase("program start")
    init, sums = ref.init_table(table["num_row"], table["num_col"], run.seed)
    run.phase("initial values")
    worker_table = mv.create_table(
        table["kind"], table["num_row"], table["num_col"],
        np.dtype(table["dtype"]), updater_type=table["updater_type"],
        init_value=init)
    run.phase("create_table")
    return worker_table, ref, sums


def make_pool(ref, mirror, zipf, rng, entries, rows_per_op, cols):
    """``entries`` pooled Adds (distinct Zipf row ids, deltas in units),
    each registered with the mirror."""
    pool = []
    for _ in range(entries):
        ids = zipf.distinct(rng, rows_per_op)
        dk = ref.delta_k(rng, rows_per_op, cols)
        mirror.add_pool(ids, dk)
        pool.append((ids, dk))
    return pool


def final_checks(run, table, ref, mirror, counts, init_sums, zipf, shape,
                 check_rows):
    """After the window: a seeded sample of rows, hot and cold, read through
    the table must equal the reference to the last bit, and the int32
    column sums of the whole table, taken on the device, must equal those
    of the initial table plus every acknowledged Add."""
    import jax
    import jax.numpy as jnp

    rows, cols = shape["num_row"], shape["num_col"]
    rng = np.random.default_rng(common.mix_seed(run.seed, 0x636865636B))
    hot = zipf.distinct(rng, min(check_rows // 2, rows // 4))
    cold = rng.choice(rows, check_rows - len(hot), replace=False)
    sample = np.unique(np.concatenate([hot, cold])).astype(np.int32)
    got = table.get(sample)
    run.compare.add("final_sample_mismatch",
                    ref.mismatches(got, mirror.rows_k(sample, counts)), 0)

    unit = ref.UNIT

    @jax.jit
    def column_sums(data):
        return jnp.sum(jnp.round(data[:rows, :cols] * unit).astype(jnp.int32),
                       axis=0)

    got_sums = np.asarray(column_sums(table.get_device())).astype(np.int64)
    want = mirror.column_sums(init_sums, counts)
    run.compare.add("checksum_mismatch_columns",
                    int(((got_sums - want) % (1 << 32) != 0).sum()), 0)
    run.result["rows_checked"] = int(len(sample))


def end_to_end(result):
    """Rows per second of acknowledged ops over the window; the median time
    of a Get and of an Add, each from the call to its completed result (the
    two differ, so a median over both together would sit between two
    humps and swing); and the 95th percentile over every op of the window."""
    times = result["op_ms"]
    both = times["add"] + times["get"]
    print(json.dumps({"op_samples": {"add": len(times["add"]),
                                     "get": len(times["get"])}}), flush=True)
    return {"rows_per_s": result["rows"] / result["elapsed_s"],
            "get_p50_ms": common.percentile(times["get"], 50),
            "add_p50_ms": common.percentile(times["add"], 50),
            "op_p95_ms": common.percentile(both, 95)}
