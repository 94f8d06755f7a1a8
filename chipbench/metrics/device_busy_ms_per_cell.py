"""Device-busy time in the traced window (union of the operations'
intervals, averaged over the devices) per cell completed in it
(milliseconds per cell): the batched sweep programs."""


def read(record):
    tr = record.get("trace")
    if not tr or not record["cells"]:
        return None
    busy = sum(tr["busy_s"]) / len(tr["busy_s"])
    return busy / record["cells"] * 1e3
