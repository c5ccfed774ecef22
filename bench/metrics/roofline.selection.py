"""Share of the roofline that the window's Greedy selections reached, as a
ratio: the least time the chip allows for their work
(``bench/work_selection.py``, from the shapes alone) over the device's
busy time in the window.

The busy time holds every operation of the window (the draw of each
ground set, its e0 column, the candidate gather and padding, the gain
kernel, the fold and the value of each round), so the metric names no
kernel and bounds the selection's own share from below. The peak is the
bf16 one at every precision."""
from bench import work, work_selection


def read(ctx):
    w = ctx.window.work.get("dense_greedy")
    if not w or ctx.reduced.busy_s <= 0:
        return None
    flops, nbytes = work_selection.dense_greedy(w["n"], w["d"], w["k"],
                                                w["itemsize"])
    least = w["calls"] * work.min_seconds(flops, nbytes, ctx.peak)
    return least / ctx.reduced.busy_s
