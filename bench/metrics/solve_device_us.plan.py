"""Device time of the solve programs per candidate, summed over the cell's
devices, from the trace."""
from bench.lib.readers import solve, value


def read(run):
    prog, n = solve(run), value(run, "candidates")
    if prog is None or not n:
        return None
    return prog["device_s"] / n * 1e6
