"""Device time per traced step, averaged over the chips, of the collective
operations that run on their own (``trace.is_collective``): the exchange
between chips that the step leaves unoverlapped. A collective that the
compiler runs inside a fusion beside a matmul (``async_collective_fusion``
in the HLO, ``fusion.N`` in the trace) is hidden behind that matmul, and
its time counts as compute, not here."""


def read(r):
    t = r["trace"]
    steps = t.get("spans", {}).get("bench.dispatch", {}).get("count") \
        if t else None
    if not steps:
        return None
    return 1e3 * t["collective_s"] / steps
