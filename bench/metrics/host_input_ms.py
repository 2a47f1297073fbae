"""Host time per step spent taking the next batch from the pipeline and
putting it on the device: the ``bench.input`` span of the traced steps."""


def read(r):
    span = r["trace"].get("spans", {}).get("bench.input")
    if not span or not span["count"]:
        return None
    return 1e3 * span["seconds"] / span["count"]
