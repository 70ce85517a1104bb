"""Device: how unevenly the traced job's work fell on the cell's chips.

``1 - mean(busy_by_chip) / max(busy_by_chip)`` from the profiler trace
(``benchmark/trace_reduce.py``), each chip's busy time being the union
of its device operations' intervals inside the job's ``run`` span, a
chip that ran nothing counting 0 s. It reads 0 when every chip is
equally busy and ``1 - 1/n`` when one of ``n`` chips does all the
work. Nothing to read on one chip."""


def read(ctx):
    red = ctx["trace"]
    if red is None or len(red["busy_by_chip"]) < 2:
        return None
    busy = red["busy_by_chip"]
    return 1.0 - sum(busy) / len(busy) / max(busy)
