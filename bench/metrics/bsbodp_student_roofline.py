"""Share of its roofline that the compiled non-leaf student step
(``jit_bsbodp_student``: edges and the cloud as students) reaches in the
profiled round, in percent: the least time the chip could take for the
round's ``student`` work (``record["trace"]["work"]``, ``bench/flops.py``),
the larger of its operations over the bf16 peak and its least HBM bytes
over the HBM bandwidth, summed by model, over the device seconds of
``jit_bsbodp_student`` in ``by_program``."""

PROGRAM, STEP = "jit_bsbodp_student", "student"


def read(record):
    tr = record.get("trace")
    device_s = tr and tr["by_program"].get(PROGRAM, {}).get("device_s")
    work = [w[STEP] for w in tr["work"].values() if STEP in w] if device_s else []
    if not work:
        return None
    peaks = record["peaks"]
    least_s = sum(max(w["flops"] / peaks["bf16_flops"], w["bytes"] / peaks["hbm_bytes_per_s"])
                  for w in work)
    return 100.0 * least_s / device_s
