"""Share of the window's live walker-steps served by the rejection
(eRJS) side of the adaptive split: ``rjs_served / live`` (the engine's
exact integer counters)."""


def read(record):
    if not record.get("live"):
        return None
    return record["rjs_served"] / record["live"]
