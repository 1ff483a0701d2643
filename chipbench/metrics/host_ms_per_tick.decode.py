"""Host milliseconds per decode tick that the chip does not wait out: the
engine's own leaf spans (``EngineStats.host``) over the window, the waits on
the device (``*.wait``) and the collector's pauses (``engine.gc``, which lie
inside other spans) left out, over the decode ticks.  None where the engine
keeps no spans or ran no tick."""


def read(run):
    host = getattr(run.stats, "host", None)
    if host is None or not run.stats.decode_steps:
        return None
    seconds = sum(s for name, (_, s) in host.items()
                  if name.startswith("engine.") and not name.endswith(".wait")
                  and name != "engine.gc")
    return 1e3 * seconds / run.stats.decode_steps
