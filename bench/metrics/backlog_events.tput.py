"""Events offered in the window but not answered by its end, from the
client's counts: the daemon's queue at the close."""
from bench.lib.readers import value


def read(run):
    return value(run, "backlog_events")
