"""How late the load generator sent: the 95th percentile of actual minus
scheduled send time over the window's events, on the client's clock."""
from bench.lib.readers import value


def read(run):
    return value(run, "gen_lag_p95_ms")
