"""Client sync: percent of the window's row launches whose ids were on the
device when the launch began, from the op trace: of the TABLE_ROW_LAUNCH
records that say who uploaded their ids (`ids_from`: `caller`, an in-process
device-path op's own thread at submit, or `dispatcher`, in the op's
TABLE_ROW_PREP), the share whose `ids_ready` is 1 (the id array's
`is_ready()` as the launch began). High where the upload rode under the
queue wait; near 0 where every launch's ids went up in its own service (a
served request's). A program whose launch records carry no `ids_from` (the
parent of the PR that brought the field) gives None."""

from benchmark import op_trace

SOURCE = "program_span"


def read(run):
    trace = op_trace.of(run)
    if trace is None:
        return None
    landed = [getattr(launch, "ids_ready", 0)
              for launch in trace.spans("TABLE_ROW_LAUNCH")
              if getattr(launch, "ids_from", "")]
    if not landed:
        return None
    return 100.0 * sum(landed) / len(landed)
