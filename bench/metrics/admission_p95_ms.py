"""The 95th percentile of admission latency over every event of the
window, on the client's clock.  Its run-to-run spread on the chip (0.38 to
0.44 of its median over six seeds) is too wide for a bound, so it is kept
as a reading of the daemon's queue, which sets the tail."""
from bench.lib.readers import value


def read(run):
    return value(run, "admission_p95_ms")
