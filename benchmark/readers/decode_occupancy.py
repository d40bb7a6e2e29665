"""Tokens the decode steps produced (``tokens_generated`` less the one
token each prefill yields) / (decode steps x slots), %: how full the
fixed decode batch ran."""


def read(context):
    c = context["counters"]
    stats = c.get("engine_stats") or {}
    if not stats.get("steps"):
        return None
    decoded = stats["tokens_generated"] - stats.get("prefills", 0)
    return 100.0 * decoded / (stats["steps"] * c["max_slots"])
