"""Pods whose bind stamp falls inside the window, per window second."""


def read(run):
    n = sum(1 for _node, stamp in run.binds.values()
            if run.t0 <= stamp < run.t1)
    return n / (run.t1 - run.t0)
