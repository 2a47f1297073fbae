"""Host time per step in ``TrainMonitor.on_step``, which ticks the hpcmd
daemon inline on the training thread when its interval is due: the
``bench.on_step`` span of the traced steps."""


def read(r):
    span = r["trace"].get("spans", {}).get("bench.on_step")
    if not span or not span["count"]:
        return None
    return 1e3 * span["seconds"] / span["count"]
