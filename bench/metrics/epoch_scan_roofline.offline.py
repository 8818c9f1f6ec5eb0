"""The staged epoch program's share of its HBM roofline, in %: the floor
bytes of the window's hops (``bench/floor_bytes.py``) over the device
time of the epoch programs, summed over chips, times the chip's peak
HBM bandwidth (``bench/peaks.json``)."""


def read(record):
    tr, peaks = record.get("trace"), record.get("peaks")
    if not tr or not peaks or tr["kernel_sum_s"]["walk"] <= 0:
        return None
    return 100.0 * record["floor_bytes"] / (
        tr["kernel_sum_s"]["walk"] * peaks["hbm_bytes_per_s"])
