"""1 - union of device-op intervals / traced window, averaged over the
chips used, %."""


def read(context):
    reduced = context["trace"]
    if reduced is None:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
