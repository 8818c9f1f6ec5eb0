"""Device time of the fused mega-step epoch programs per scan step, in
ms: the trace's time in the walk's epoch program (module
``jit_epoch_fused``, the slowest chip's) divided by the scan steps the
window ran."""


def read(record):
    tr = record.get("trace")
    if not tr or not record.get("scan_steps"):
        return None
    return 1e3 * tr["kernel_max_s"]["walk"] / record["scan_steps"]
