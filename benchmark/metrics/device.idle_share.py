"""Device: share of the traced job in which the chip ran nothing.

``1 - busy / window`` from the profiler trace
(``benchmark/trace_reduce.py``): busy is the union of the device
operations' intervals, the window the program's ``run`` span."""


def read(ctx):
    red = ctx["trace"]
    return None if red is None else red["idle_share"]
