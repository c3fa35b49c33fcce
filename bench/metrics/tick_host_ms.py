"""Host time per engine tick: the wall of the benchmark's ``bench.tick``
span minus the device busy time inside it, averaged over the traced ticks."""


def read(run):
    t = run.get("trace")
    s = t and t["spans"].get("bench.tick")
    if not s or not s["count"]:
        return None
    return 1e3 * (s["total_s"] - s["device_busy_s"]) / s["count"]
