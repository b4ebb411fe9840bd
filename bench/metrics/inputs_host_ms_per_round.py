"""Host milliseconds per round drawing BSBODP's bridge and local rows and
copying them to the device: the program tracer's ``bsbodp.inputs`` spans."""


def read(record):
    return record.get("span_ms", {}).get("bsbodp.inputs")
