"""Overlap alignment: the native aligner's redo, ns per band cell.

``align_seconds_native_redo / align_native_cells`` in the traced job's
metrics registry, times 1e9: the wall time of the ``native_redo`` span
(operands, encoding and the native aligner) over the band cells the
native aligner computed in every pass of every overlap it redid. Nothing
to read where nothing was redone, or from a program without the cell
counter."""


def read(ctx):
    reg = ctx["registry"]
    cells = reg.get("align_native_cells", 0)
    if not cells:
        return None
    return reg.get("align_seconds_native_redo", 0.0) / cells * 1e9
