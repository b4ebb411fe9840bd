"""Host milliseconds per round slicing coalesced groups' stacked state back
to the nodes: the program tracer's ``bsbodp.unstack`` spans."""


def read(record):
    return record.get("span_ms", {}).get("bsbodp.unstack")
