"""One of the driver's counters as it stands (``None`` leaves the metric
out where the driver counted no such thing)."""


def read(context, name):
    return context["counters"].get(name)
