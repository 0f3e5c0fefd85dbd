"""99th percentile of the engine's pod_queue_wait_s histogram over the
window (fixed buckets, interpolated: a per-layer statistic only)."""
import math

from benchmark.layers import hist_quantile


def read(run):
    v = hist_quantile(run.hist_delta("pod_queue_wait_s"), 0.99)
    return None if math.isnan(v) else v * 1e3
