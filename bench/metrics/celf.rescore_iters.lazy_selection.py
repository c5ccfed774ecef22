"""CELF's re-score trips a selection, mean over the window's calls: the
program's counter ``OptResult.rescore_trips`` (``src/repro/core/
tracing.py``), the iterations of the lazy step's while loop summed over
the rounds. None where the program reports no such counter."""


def read(ctx):
    w = ctx.window.work.get("celf")
    trips = w and w.get("rescore_trips")
    if not trips:
        return None
    return sum(trips) / len(trips)
