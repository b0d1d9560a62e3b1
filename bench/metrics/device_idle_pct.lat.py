"""Share of the window in which the device ran no operation, from the
trace, averaged over the cell's devices."""
from bench.lib.readers import idle_pct as read  # noqa: F401
