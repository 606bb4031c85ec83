"""Table op: percent, the distinct rows of 128 the lane kernel walked for the
window's keyed FTRL Adds over the key slots those launches walked (their
TABLE_ROW_LAUNCH records' `n`). The count of rows is the Add program's third
result, which the program leaves on the device beside the id of the launch's
record (`multiverso_tpu.tables.row_plan.ROWS_WALKED`, filled only while the
op trace records; the program fetches none of them): this reader joins the
pairs to the window's records by id and fetches the counts here, once, after
the window. The kernel issues its descriptors a row, not a slot: about 59
where 111,000 Zipf keys of a step live in 67,500 rows, 100 where every slot
has a row of its own and walking rows saves nothing. None on a program that
keeps no such pairs (the parent of the PR that brought them), and where no
launch of the window has one."""

from benchmark import op_trace

SOURCE = "program_span"


def read(run):
    trace = op_trace.of(run)
    if trace is None:
        return None
    try:
        from multiverso_tpu.tables.row_plan import ROWS_WALKED
    except ImportError:
        return None
    slots = {launch.id: launch.n
             for launch in trace.spans("TABLE_ROW_LAUNCH")}
    walked = [(slots[launch], rows) for launch, rows in list(ROWS_WALKED)
              if launch in slots]
    if not walked:
        return None
    import jax
    rows = sum(int(count) for count in jax.device_get(
        [rows for _, rows in walked]))
    return 100.0 * rows / sum(n for n, _ in walked)
