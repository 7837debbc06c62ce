"""Time the round loop waited for a staged batch, per round of the window
(``FederatedBatcher.stall_seconds``, counted by the program), in ms: the
build time that the prefetch failed to hide."""


def read(run):
    return run.stall_s / run.rounds * 1e3 if run.rounds else None
