"""How long the first chips wait for the last, mean over submits: the
largest minus the smallest ``RunResult.device_finish`` over the chip
groups (the ROI-clock time each group's last packet finished, stamped
after every packet; a group that ran no packet reads 0).  This is the
split that HGuided controls when the chips are alike.  None where the
runtime does not report it."""


def read(run):
    xs = []
    for s in run.submits:
        finish = getattr(s.result, "device_finish", None)
        if not finish:
            return None
        chips = [finish[g] for g in run.chip_groups]
        xs.append(max(chips) - min(chips))
    return sum(xs) / len(xs) if xs else None
