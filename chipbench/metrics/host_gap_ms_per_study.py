"""Device-idle time inside the benchmark's `study` spans, per Study
(milliseconds, averaged over the devices): the host path of `Study.run`
(plan, column building, transfers, result fetch, frame)."""


def read(record):
    tr = record.get("trace")
    if not tr or not tr["studies"]:
        return None
    idle = sum(tr["idle_in_studies_s"]) / len(tr["idle_in_studies_s"])
    return idle / tr["studies"] * 1e3
