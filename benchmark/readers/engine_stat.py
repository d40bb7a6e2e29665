"""One field of one latency family of ``ServeEngine.stats()`` over the
window (the metrics are reset when the window opens), scaled."""


def read(context, family, field, scale=1.0):
    row = (context["counters"].get("engine_stats") or {}).get(family)
    if not row or row.get(field) is None:
        return None
    return row[field] * scale
