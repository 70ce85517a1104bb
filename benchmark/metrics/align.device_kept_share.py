"""Overlap alignment: share of the overlaps the device aligned.

``ovl_device_jobs / (ovl_device_jobs + ovl_native_jobs)``: overlaps
whose breaking points the device produced, over all overlaps aligned.
The rest went to the native aligner (over budget, or uncertified and
redone after the device attempt). Nothing to read where no overlap
needed aligning (SAM input)."""


def read(ctx):
    reg = ctx["registry"]
    dev = reg.get("ovl_device_jobs", 0)
    nat = reg.get("ovl_native_jobs", 0)
    if dev + nat == 0:
        return None
    return dev / (dev + nat)
