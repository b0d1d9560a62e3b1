"""Share of its roofline that the Alg. 4.1 candidate middle reaches: the
least time of the window's algorithmic work on one chip (the larger of
operations at the bf16 compute peak and bytes at the memory bandwidth,
``bench/lib/peaks.json``) over the solve programs' device time summed
over the cell's devices.  The work counts only what the algorithm needs
(``bench/lib/workcount.py``), whatever implements it."""
from bench.lib import workcount
from bench.lib.readers import solve


def read(run):
    prog, work = solve(run), run.get("work")
    if prog is None or not work or work["ops"] <= 0:
        return None
    t, _ = workcount.least_time(work["ops"], work["bytes"],
                                workcount.peaks(run["device_kind"]))
    return 100.0 * t / prog["device_s"]
