"""Mean per submit of the seconds the runtime spent committing packet
results (copy back, write into the output, journal):
``RunResult.commit_s``, the summed length of the run's
``coexec.commit`` spans.  None where the runtime does not report it."""


def read(run):
    xs = [getattr(s.result, "commit_s", None) for s in run.submits]
    if not xs or None in xs:
        return None
    return sum(xs) / len(xs)
