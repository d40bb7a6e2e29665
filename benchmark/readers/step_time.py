"""Device-synced epoch time / steps, ms."""


def read(context):
    c = context["counters"]
    if not c.get("steps"):
        return None
    return sum(c["epoch_s"]) / c["steps"] * 1e3
