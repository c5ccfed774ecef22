"""Share of the roofline that the window's multiset evaluations reached, as
a ratio: the least time the chip allows for their work (``bench/work.py``,
from the shapes alone) over the device's busy time in the window.

The busy time holds every operation of the window (the index draw, the
gather, padding and whatever kernel evaluates the sets), so the metric
names no kernel and bounds the evaluation's own share from below. The
peak is the bf16 one at every precision."""
from bench import work


def read(ctx):
    w = ctx.window.work.get("multiset_eval")
    if not w or ctx.reduced.busy_s <= 0:
        return None
    flops, nbytes = work.multiset_eval(w["n"], w["l"], w["k"], w["d"],
                                       w["itemsize"])
    least = w["calls"] * work.min_seconds(flops, nbytes, ctx.peak)
    return least / ctx.reduced.busy_s
