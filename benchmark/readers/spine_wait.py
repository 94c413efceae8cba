"""Mean time a dispatch of one spine stage waited for its lane, in ms:
``/api/status`` ``dispatch.spine.stages[stage]`` at the window's two ends."""


def _stage(status, stage):
    try:
        return status["dispatch"]["spine"]["stages"].get(stage)
    except (KeyError, TypeError):
        return None


def read(ctx, stages):
    wait = count = 0.0
    for stage in stages:
        a = _stage(ctx["before"].get("status"), stage)
        b = _stage(ctx["after"].get("status"), stage)
        if not b:
            continue
        wait += b["queue_wait_s"] - (a["queue_wait_s"] if a else 0.0)
        count += b["count"] - (a["count"] if a else 0)
    if count <= 0:
        return None
    return 1e3 * wait / count
