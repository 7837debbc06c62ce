"""Window assembly per window (``ServingEngine.stats["build_seconds"]``,
counted by the program, over the windows the stream fills), in ms."""


def read(run):
    return run.build_s / run.windows * 1e3 if run.windows else None
