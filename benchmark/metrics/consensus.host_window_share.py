"""Consensus: share of windows polished on the host path.

``poa_host_windows / poa_windows_total``: windows the engine sent to
the host (admission: too wide or too long for a device chunk), over
all windows polished."""


def read(ctx):
    reg = ctx["registry"]
    total = reg.get("poa_windows_total", 0)
    if not total:
        return None
    return reg.get("poa_host_windows", 0) / total
