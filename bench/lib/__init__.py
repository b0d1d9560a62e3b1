"""The benchmark's yardstick: traffic, reference, trace reduction, work counts.

Nothing here imports the program under test; the drivers do.
"""
