"""Run a cell with the timed path broken underneath (CPU rehearsal).

    JAX_PLATFORMS=cpu python -m benchmark.tests.faulty <fault> <run.py args>

Faults, each planted where the scheduler's answer is produced or kept:

- `altered`: every bulk bind writes all its pods to the node the engine
  chose for the first of them (answers altered where they are produced);
- `half`: every bulk bind stores only the first half of its pods and
  reports all of them bound (half of each batch left out);
- `stale`: the assignment scan returns the free capacity it was given,
  so the next batch starts from the state before this one (a step that
  returns its state unchanged).
"""
import sys


def plant(fault: str) -> None:
    from minisched_tpu.state.store import ClusterStore

    real_bind = ClusterStore.bind_pods
    if fault == "altered":
        def bind_pods(self, assignments):
            assignments = list(assignments)
            first = assignments[0][1] if assignments else ""
            real_bind(self, [(k, first) for k, _n in assignments])
            return [k for k, _n in assignments]
        ClusterStore.bind_pods = bind_pods
    elif fault == "half":
        def bind_pods(self, assignments):
            assignments = list(assignments)
            real_bind(self, assignments[:len(assignments) // 2])
            return [k for k, _n in assignments]
        ClusterStore.bind_pods = bind_pods
    elif fault == "stale":
        from minisched_tpu.ops import select

        real_scan = select.greedy_assign_shortlist

        def unchanged(scores, requests, free0, key, k=128):
            r = real_scan(scores, requests, free0, key, k=k)
            return r._replace(free_after=free0)
        select.greedy_assign_shortlist = unchanged
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    fault = sys.argv[1]
    sys.argv = [os.path.join(root, "benchmark", "run.py")] + sys.argv[2:]
    plant(fault)
    from benchmark import run

    sys.exit(run.main(sys.argv[1:]))
