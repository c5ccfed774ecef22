"""Host time of building the function object, ``ExemplarClustering``'s
constructor (its e0 column and the read of L({e0})), in ms per call: the
program span ``function.init`` (``src/repro/core/tracing.py``) over the
window, divided by the number of times it opened. None where the program
opens no such span."""


def read(ctx):
    count, seconds = ctx.reduced.spans.get("function.init", (0, 0.0))
    return 1e3 * seconds / count if count else None
