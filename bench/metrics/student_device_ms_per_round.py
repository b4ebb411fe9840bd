"""Device milliseconds per profiled round of the compiled student steps:
the operations of ``jit_bsbodp_student`` and ``jit_bsbodp_student_leaf``
in the trace's ``by_program``."""

PROGRAMS = ("jit_bsbodp_student", "jit_bsbodp_student_leaf")


def read(record):
    tr = record.get("trace")
    ms = [tr["by_program"][p]["device_s"] * 1e3 for p in PROGRAMS
          if tr and p in tr["by_program"]]
    return sum(ms) / tr["rounds"] if ms else None
