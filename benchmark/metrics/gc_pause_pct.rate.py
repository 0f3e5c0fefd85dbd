"""Share of the whole measured window that the process stood still in
Python's cyclic collections, every generation (the engine's
gc_pause_s_total): a traced part of a few seconds often holds no full
collection. None where the program has no such counter."""


def read(run):
    name = "gc_pause_s_total"
    if name not in run.engine0 or run.t1 <= run.t0:
        return None
    return run.delta(name) / (run.t1 - run.t0) * 100.0
