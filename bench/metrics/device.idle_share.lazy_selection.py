"""Share of the traced window in which no operation ran on the device, in
%: one minus the union of the device's operation intervals over the
window (``bench/trace.py``), as ``device.idle_share.selection`` reads it."""


def read(ctx):
    r = ctx.reduced
    if r.window_s <= 0 or r.devices == 0:
        return None
    return 100.0 * r.idle_share
