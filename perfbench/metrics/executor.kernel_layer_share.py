"""Share of the window's layer executions that ran on the Pallas kernel,
from the program's ``pallas.layer.<path>`` counters."""


def read(ctx):
    total = sum(ctx.counters.values())
    if not total:
        return None
    return 100.0 * ctx.counters.get("pallas.layer.kernel", 0) / total
