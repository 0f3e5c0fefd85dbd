"""Mean time from a pod's creation to its first entry into the
scheduling queue (the informer's lag), over the pods bound in the traced
part of the window: delta sum / delta count of the engine's
pod_informer_lag_s histogram. None where the program has no such
histogram."""


def read(run):
    name = "pod_informer_lag_s"
    h0 = run.engine0.get("histograms", {}).get(name)
    h1 = (run.traced1 or run.engine1).get("histograms", {}).get(name)
    if h0 is None or h1 is None:
        return None
    n = h1["count"] - h0["count"]
    return (h1["sum"] - h0["sum"]) / n * 1e3 if n > 0 else None
