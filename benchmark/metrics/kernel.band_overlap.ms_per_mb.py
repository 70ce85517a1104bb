"""Kernels: band-kernel device time in the overlap programs, ms per Mb.

Device milliseconds of the Pallas band kernels' operations
(``fw_dirs_band`` and ``fw_dirs_band_tile``, ``ops/pallas/band_kernel.py``)
inside the overlap aligner's programs (``_chunk_breaking_points``,
``_tiled_chunk_breaking_points``, ``ops/ovl_align.py``), from the
profiler trace, per Mb of draft polished in the traced job."""

from benchmark import trace_reduce


def read(ctx):
    secs = trace_reduce.kernel_seconds(ctx["trace"], r"^fw_dirs_band",
                                       overlap=True)
    return None if secs is None else 1e3 * secs / ctx["draft_mb"]
