"""Share of the traced window in which no op ran on a device, averaged
over the cell's devices."""
from bench import trace_reduce as tr


def read(trace, ctx):
    busy = tr.busy_seconds(trace)
    window = tr.window_seconds(trace)
    idle = [1 - b / window for b in busy.values()]
    return 100 * sum(idle) / len(idle), "%"
