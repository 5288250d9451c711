"""1 minus the union of the device's operations over the window (%), in a
serving cell. Reads the trace; moves itl_p99_ms."""


def read(trace, counts, cell):
    if not trace.ops.get(trace.chips[0]):
        return None
    return 100.0 * trace.idle_share
