"""The commit time on the critical path, mean over submits: the largest
of ``RunResult.device_commit_s``, each device's summed ``coexec.commit``
steps (copy back, output write, journal), where ``commit_s`` sums them
over every device thread.  None where the runtime does not report it."""


def read(run):
    xs = []
    for s in run.submits:
        per_device = getattr(s.result, "device_commit_s", None)
        if not per_device:
            return None
        xs.append(max(per_device))
    return sum(xs) / len(xs) if xs else None
