"""Share of the chip's bf16 peak that the profiled round's model operations
make of the device's busy time, in percent."""


def read(record):
    tr = record.get("trace")
    if not tr or not tr["ops"] or not tr["busy_s"]:
        return None
    return 100.0 * tr["ops"] / tr["busy_s"] / record["peaks"]["bf16_flops"]
