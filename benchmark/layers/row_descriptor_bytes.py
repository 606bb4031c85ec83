"""Kernel: bytes one DMA descriptor of the row scatter-add carries, from the
op trace: over the window's TABLE_ROW_LAUNCH records that issued descriptors
(the Pallas path), the bytes of table rows they moved over the descriptors
they issued. 512 for a table of one lane tile; 1,536 where a row of three
tiles is one strided descriptor, 512 where it is three. A program whose
launch records carry neither (the parent of the PR that brought them) gives
None."""

from benchmark import op_trace

SOURCE = "program_span"


def read(run):
    trace = op_trace.of(run)
    if trace is None:
        return None
    issued = [r for r in trace.spans("TABLE_ROW_LAUNCH")
              if getattr(r, "descriptors", 0)]
    if not issued:
        return None
    return sum(r.bytes for r in issued) / sum(r.descriptors for r in issued)
