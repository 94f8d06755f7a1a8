"""1 - device-busy time / the traced window, averaged over the devices
(a fraction)."""


def read(record):
    tr = record.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    busy = sum(tr["busy_s"]) / len(tr["busy_s"])
    return 1.0 - busy / tr["window_s"]
