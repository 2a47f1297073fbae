"""Share of the traced window in which no operation ran on the device,
in percent: 1 - (union of device-operation intervals / window)."""


def read(r):
    t = r["trace"]
    if not t or not t.get("window_s"):
        return None
    return 100.0 * t["idle_share"]
