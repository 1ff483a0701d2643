"""Mean number of busy lanes per decode tick (the engine's own counter)."""


def read(run):
    occ = run.stats.occupancy
    return sum(occ) / len(occ) if occ else None
