"""The fused mega-step epoch programs' share of their HBM roofline, in %:
the floor bytes of the window's deepwalk hops
(``floor_bytes.hop_floor_bytes``, one hop per live walker-step of the
``live`` counter) over the epoch programs' device time, summed over
chips, times the chip's peak HBM bandwidth (``bench/peaks.json``)."""
import floor_bytes


def read(record):
    tr, peaks = record.get("trace"), record.get("peaks")
    if not tr or not peaks or not record.get("live") \
            or tr["kernel_sum_s"]["walk"] <= 0:
        return None
    return 100.0 * floor_bytes.hop_floor_bytes(
        "deepwalk", hops=record["live"]) / (
        tr["kernel_sum_s"]["walk"] * peaks["hbm_bytes_per_s"])
