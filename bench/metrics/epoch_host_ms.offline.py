"""Host time per scheduler epoch, in ms: the benchmark's span around each
``run_epoch`` minus the device busy time inside it, averaged over the
window's epochs (the EpochScheduler's refill, host sync and harvest)."""


def read(record):
    tr = record.get("trace")
    spans = (tr or {}).get("spans", {}).get("bench.run_epoch")
    if not spans:
        return None
    return 1e3 * sum(dur - busy for dur, busy in spans) / len(spans)
