"""Device time of the solve program per flush (``core/game.py``
``_solve_batch_jit``), from the trace."""
from bench.lib.readers import solve_ms_per_run as read  # noqa: F401
