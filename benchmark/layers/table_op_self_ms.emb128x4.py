"""Table op: mean host milliseconds of one table Add or Get less its device
dispatch calls and its blocking fetch; `table_op_self_ms`' arithmetic, for
the cell of the table sharded over four chips (routing the ids to their
shards, TABLE_ROW_ROUTE, is in it)."""

from benchmark import common

SOURCE = "program_span"


def read(run):
    return common.load_module("layers", "table_op_self_ms").read(run)
