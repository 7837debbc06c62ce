"""Execution per micro-batch (``ServingEngine.stats["execute_seconds"]``
over ``batches``, counted by the program around ``block_until_ready``),
in ms."""


def read(run):
    return run.execute_s / run.batches * 1e3 if run.batches else None
