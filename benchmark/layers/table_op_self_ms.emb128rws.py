"""Table op: mean host milliseconds of one table Add or Get less its device
dispatch calls and its blocking fetch; `table_op_self_ms`' arithmetic, for
the cell whose Adds are optimizer steps (the host does nothing for the
state: the option's scalars are cached device constants)."""

from benchmark import common

SOURCE = "program_span"


def read(run):
    return common.load_module("layers", "table_op_self_ms").read(run)
