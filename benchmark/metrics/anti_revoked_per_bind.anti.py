"""Pods revoked for a required anti-affinity conflict with an earlier
pod of their own batch, per pod bound: the retry work intra-batch
arbitration costs. None where the engine keeps no such counter."""

COUNTER = "anti_revoked_total"


def read(run):
    end = run.traced1 or run.engine1
    if COUNTER not in end or COUNTER not in run.engine0:
        return None
    bound = run.layer_delta("pods_bound")
    return run.layer_delta(COUNTER) / bound if bound > 0 else None
