"""XLA compiles (loads from the persistent cache included) that the engine
counted after its warm-up.  None where the engine does not count them."""


def read(run):
    return getattr(run.stats, "compiles", None)
