"""Mean per submit of the runtime's staging phases on the critical path:
``phases.h2d_s + phases.d2h_s`` (the stage-in wave before the first
packet computes, and the commit tail after the queue drains)."""


def read(run):
    xs = [s.result.phases.h2d_s + s.result.phases.d2h_s
          for s in run.submits if s.result.phases is not None]
    return sum(xs) / len(xs) if xs else None
