"""minisched_tpu — a TPU-native scheduling framework.

A from-scratch rebuild of the capabilities of shopetan/mini-kube-scheduler
(reference at /root/reference): a simulated cluster (event-sourced state store
with watch streams in place of the in-process kube-apiserver + etcd,
reference k8sapiserver/k8sapiserver.go:43), a scheduling queue with
event-driven requeue and backoff (reference minisched/queue/queue.go), a
plugin framework with Filter/PreScore/Score/NormalizeScore/Permit/Bind
extension points (reference minisched/minisched.go:115-277), asynchronous
permit-wait and binding (reference minisched/waitingpod/waitingpod.go), a
per-decision explainability store (reference scheduler/plugin/resultstore/
store.go), and a programmable scenario runner (reference sched.go:70-143).

The idiomatic shift from the reference: instead of a sequential per-pod ×
per-node × per-plugin Go loop (reference minisched/minisched.go:124-137,
167-185), plugins emit (pending_pods × nodes) constraint masks and score
matrices evaluated in a single JAX/XLA step, and host selection is a
capacity-aware greedy scan (or joint-assignment auction) over the score
matrix, sharded over a node-axis device mesh at scale.
"""

__version__ = "0.1.0"
