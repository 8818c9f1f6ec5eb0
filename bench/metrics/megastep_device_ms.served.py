"""Device time of the fused mega-step kernel per scan step, in ms: the
trace's time in the Pallas mega-step summed over both tenants, divided
by the scan steps their epochs ran in the window."""


def read(record):
    tr = record.get("trace")
    if not tr or not record.get("scan_steps"):
        return None
    return 1e3 * tr["kernel_max_s"]["walk"] / record["scan_steps"]
