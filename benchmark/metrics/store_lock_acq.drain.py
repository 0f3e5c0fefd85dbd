"""Times the cluster store's lock was taken, per batch (the engine's
store_lock_acquisitions_total, counted while the recorder is armed: the
traced part of the window). None where the program has no such
counter."""
from benchmark.layers import per_batch


def read(run):
    if "store_lock_acquisitions_total" not in run.engine0:
        return None
    return per_batch(run, "store_lock_acquisitions_total")
