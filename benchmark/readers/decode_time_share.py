"""Share of the serve loop's busy window (first step start to last step
end, ``busy_s``) spent inside device-synced decode steps
(``decode_step_s`` count x mean), %.  The rest is prefill chunks and
host work."""


def read(context):
    stats = context["counters"].get("engine_stats") or {}
    step = stats.get("decode_step_s")
    if not step or not stats.get("busy_s"):
        return None
    return 100.0 * step["count"] * step["mean_s"] / stats["busy_s"]
