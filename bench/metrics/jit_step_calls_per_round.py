"""Calls of the jitted BSBODP distill steps per measured round: the change
of the program's ``fl_jit_calls_total`` counter, summed over its ``step``
labels (decode, teacher, student)."""


def read(record):
    calls = [v for k, v in record.get("counters", {}).items()
             if k.split("{", 1)[0] == "fl_jit_calls_total"]
    return sum(calls) if calls else None
