"""Share of the traced slice in which no operation ran on the device, in
% (1 - union of op intervals / slice, averaged over the chips)."""


def read(ctx):
    trace = ctx.get("trace") or {}
    if not trace.get("devices") or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
