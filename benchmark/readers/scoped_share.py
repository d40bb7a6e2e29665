"""Share of the first chip's exclusive device time whose op carries one
of the program's named scopes, %: the health of the scope
instrumentation itself (what is left is ``unscoped``)."""
from benchmark.lib import scopes


def read(context):
    joined = scopes.of(context)
    if joined is None or joined["total_s"] <= 0:
        return None
    return 100.0 * joined["scoped_s"] / joined["total_s"]
