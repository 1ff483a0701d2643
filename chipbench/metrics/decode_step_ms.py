"""Device milliseconds per call of the engine's decode program."""


def read(run):
    if run.trace is None:
        return None
    seconds, calls = run.trace.programs["decode_fn"]
    return seconds / calls * 1e3 if calls else None
