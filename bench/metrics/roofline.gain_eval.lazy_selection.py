"""Share of the roofline that the ``gain_eval`` kernel reached in the
window's CELF selections, as a ratio: the least time the chip allows for
the seeding and re-score scoring (``bench/work_lazy.scoring``, from the
shapes and each call's checked evaluation count) over the device time of
the operations whose name carries ``gain_eval`` (the Pallas kernel's
``name``, as the breakdown's top operations list it). None where no such
operation ran in the window."""
from bench import work, work_lazy

KERNEL = "gain_eval"


def read(ctx):
    w = ctx.window.work.get("celf")
    kernel_s = sum(s for name, s in ctx.reduced.top_ops
                   if KERNEL in name.rsplit(":", 1)[-1])
    if not w or kernel_s <= 0:
        return None
    least = sum(work.min_seconds(*work_lazy.scoring(
        w["n"], w["d"], e, w["top_b"], w["itemsize"]), ctx.peak)
        for e in w["evaluations"])
    return least / kernel_s
