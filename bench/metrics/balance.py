"""The paper's balance, mean over submits: the first device to finish
over the last (``T_FD / T_LD``); 1 means they finished together.  The
arithmetic is copied from ``repro.core.metrics.balance``, so that the
program cannot change the yardstick.

A device's finish is taken as its busy time in the run
(``RunResult.device_busy``): packets start at the ROI window's start and
run back to back, so a device finishes its share after its busy time.
The program's ``device_finish`` is stamped when a device's loop exits,
which is after the whole queue has drained, so it reads near 1 whatever
the split."""


def balance(finish):
    fin = [t for t in finish if t > 0]
    if len(fin) <= 1:
        return 1.0
    return min(fin) / max(fin)


def read(run):
    xs = [balance(s.result.device_busy) for s in run.submits]
    return sum(xs) / len(xs) if xs else None
