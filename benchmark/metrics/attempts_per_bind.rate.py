"""Pods entering batches per pod bound in the window: work spent on
revocation and retry."""


def read(run):
    bound = run.layer_delta("pods_bound")
    return run.layer_delta("pods_seen") / bound if bound > 0 else None
