"""Mean per submit of the time device threads were blocked on the
scheduler's hand-off (``RunResult.sched_wait_s``, summed over devices)."""


def read(run):
    xs = [sum(s.result.sched_wait_s) for s in run.submits]
    return sum(xs) / len(xs) if xs else None
