"""On-chip benchmark of the runtime capacity allocator (see BENCHMARK.json).

Run one cell once from the root of a checkout::

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
