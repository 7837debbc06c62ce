"""Host batch build per round built (``FederatedBatcher.build_seconds``
over ``rounds_built``, both counted by the program), in ms."""


def read(run):
    return run.build_s / run.built * 1e3 if run.built else None
