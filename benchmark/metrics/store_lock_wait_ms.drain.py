"""Time callers waited to take the cluster store's lock, per batch (the
engine's store_lock_wait_s_total, counted while the recorder is armed:
the traced part of the window). None where the program has no such
counter."""
from benchmark.layers import per_batch


def read(run):
    if "store_lock_wait_s_total" not in run.engine0:
        return None
    v = per_batch(run, "store_lock_wait_s_total")
    return None if v is None else v * 1e3
