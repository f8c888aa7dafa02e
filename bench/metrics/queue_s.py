"""Mean per submit of the time from ``submit()`` to the run starting on
a session thread (``RunResult.queue_s``): the dispatcher's wake-up and
the hand-off to the pool.  None where the runtime does not report it."""


def read(run):
    xs = [getattr(s.result, "queue_s", None) for s in run.submits]
    if not xs or None in xs:
        return None
    return sum(xs) / len(xs)
