"""Host milliseconds per round enqueueing the jitted student steps: the
program tracer's ``bsbodp.student`` spans."""


def read(record):
    return record.get("span_ms", {}).get("bsbodp.student")
