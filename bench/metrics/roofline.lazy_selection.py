"""Share of the roofline that the window's CELF selections reached, as a
ratio: the least time the chip allows for their work
(``bench/work_lazy.py``, from the shapes and each call's checked
evaluation count) over the device's busy time in the window.

The busy time holds every operation of the window (the draw of each
ground set, its e0 column, the bound seeding, each re-score trip's
``top_k``, gather and gain kernel, the folds and the values), so the
metric names no kernel and bounds the selection's own share from below.
The peak is the bf16 one at every precision."""
from bench import work, work_lazy


def read(ctx):
    w = ctx.window.work.get("celf")
    if not w or ctx.reduced.busy_s <= 0:
        return None
    least = sum(work.min_seconds(*work_lazy.celf(
        w["n"], w["d"], w["k"], e, w["top_b"], w["itemsize"]), ctx.peak)
        for e in w["evaluations"])
    return least / ctx.reduced.busy_s
