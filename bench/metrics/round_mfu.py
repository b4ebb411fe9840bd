"""Share of the chip's bf16 peak that the profiled round's model operations
(``bench/flops.py``) make of the round's wall time, in percent."""


def read(record):
    tr = record.get("trace")
    if not tr or not tr["ops"]:
        return None
    return 100.0 * tr["ops"] / tr["window_s"] / record["peaks"]["bf16_flops"]
