"""Share of the traced window the informer's dispatch thread spent
delivering watch events to the queue and the cache (the engine's
informer_busy_s_total). None where the program has no such counter."""


def read(run):
    name = "informer_busy_s_total"
    if run.trace is None or name not in run.engine0:
        return None
    return run.layer_delta(name) / run.trace.window_s * 100.0
