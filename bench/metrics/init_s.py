"""Mean per submit of the runtime's init phase (``phases.init_s``):
executable builds and buffer registration, which a BINARY submit pays
every time."""


def read(run):
    xs = [s.result.phases.init_s for s in run.submits
          if s.result.phases is not None]
    return sum(xs) / len(xs) if xs else None
