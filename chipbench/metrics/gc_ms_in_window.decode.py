"""Milliseconds the garbage collector paused the engine after its warm-up
(the engine's ``engine.gc`` span; 0 where it never ran).  None where the
engine keeps no spans."""


def read(run):
    host = getattr(run.stats, "host", None)
    if host is None:
        return None
    return 1e3 * host.get("engine.gc", [0, 0.0])[1]
