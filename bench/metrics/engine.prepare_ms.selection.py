"""Host time of ``run_selection`` before it dispatches the selection
program, in ms per call: the program span ``run_selection.prepare``
(``src/repro/core/tracing.py``) over the window, divided by the number of
times it opened. None where the program opens no such span."""


def read(ctx):
    count, seconds = ctx.reduced.spans.get("run_selection.prepare", (0, 0.0))
    return 1e3 * seconds / count if count else None
