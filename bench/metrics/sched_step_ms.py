"""Host milliseconds per round in the wireless scheduler: the harness's
``sched_step`` span around ``scheduler.step(r)``, ``cohort_report`` and
the mask's transfer, over the traced rounds."""


def read(out):
    if not out.rounds_traced or "sched_step" not in out.span_s:
        return None
    return 1e3 * out.span_s["sched_step"] / out.rounds_traced
