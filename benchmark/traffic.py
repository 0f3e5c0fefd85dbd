"""One general traffic generator, driven by a traffic file.

A traffic file names an arrival process and its parameters:

    {"arrivals": "closed", "backlog": 4096, ...}
        keep at least `backlog` pods pending: every pod bound is replaced
    {"arrivals": "poisson", "rate_per_s": 2000.0, ...}
        open loop: exactly rate x seconds pods, due at Poisson arrival
        times (a fixed set of gaps, shuffled by the seed)

and, for both, `churn`: "delete_oldest_per_bind" deletes one bound pod,
oldest first (the standing population before any other), per pod bound,
so the population stays level; "delete_oldest_incoming_per_bind"
deletes, per pod bound, the oldest bound pod of the incoming template,
so the standing population stays and the incoming one stays at what the
warm-up's bursts bound (churn starts after them). Then the warm-up the
traffic needs: `warmup_bursts` (pods created at once, one batch size
each), `warmup_refresh` (bound standing pods on that many distinct nodes
deleted and put back, each with its own template, so one batch meets
that many changed nodes), then the traffic itself for `warmup_batches`
batches and `warmup_s` seconds.

The driver is one thread. It watches the store for binds (the only way
it learns of them), deletes for churn, and creates the pods due. It
records what every pod went through, for the metrics and the check.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from .workload import check_template, program_pod, to_program_pod


def poisson_offsets(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Arrival offsets in [0, seconds): exactly round(rate x seconds)
    arrivals whose gaps are the exponential quantiles of that count,
    rescaled to the window and shuffled by the seed. Every seed offers the
    same work; only the order of the gaps changes."""
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    np.random.default_rng(seed).shuffle(gaps)
    return (np.cumsum(gaps) - gaps[0]) * (seconds / gaps.sum())


def arrivals(traffic: dict, seconds: float, seed: int,
             scale: int = 1) -> np.ndarray:
    """Open-loop arrival offsets for `seconds` at the file's rate."""
    return poisson_offsets(traffic["rate_per_s"] / scale, seconds, seed)


CHURN = (None, "delete_oldest_per_bind", "delete_oldest_incoming_per_bind")


class Driver:
    """Creates, watches and deletes pods on the cluster store."""

    def __init__(self, store, template: dict, traffic: dict,
                 standing: Dict[str, str], template_of: Dict[str, dict]):
        self.store = store
        check_template(template)
        self.template = template
        # the template of every pod the driver binds itself (standing and
        # put back), by key
        self.template_of = template_of
        churn = traffic.get("churn")
        if churn not in CHURN:
            raise ValueError(f"churn {churn!r}: one of {CHURN}")
        self.churn = churn is not None
        # bound pods, oldest first: the standing population, then every
        # pod the driver sees bound (in `incoming` instead, where churn
        # takes incoming pods only)
        self.fifo = collections.deque(standing)
        self.incoming = (collections.deque()
                         if churn == "delete_oldest_incoming_per_bind"
                         else self.fifo)
        self.node_of = dict(standing)         # bound pod -> node
        self.binds: Dict[str, tuple] = {}     # key -> (node, bind stamp)
        self.rebinds = 0                      # bind transitions seen twice
        self.created: Dict[str, tuple] = {}   # key -> (created, due)
        self.deleted: Dict[str, float] = {}   # key -> deletion time
        self.put_back: Dict[str, tuple] = {}  # key -> (node, created)
        self.late: List[float] = []           # open loop: created - due
        self._seq = 0
        self._watch = store.watch(kinds=["Pod"])
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._mode = "idle"
        self._backlog = 0
        self._due: Optional[np.ndarray] = None
        self._due_next = 0
        self._todo: List[Callable[[], None]] = []
        self._thread: Optional[threading.Thread] = None
        self.error: Optional[BaseException] = None

    # ---- control (caller's thread) -------------------------------------

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-driver")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
        self._watch.stop()
        if self.error is not None:
            raise RuntimeError("traffic driver failed") from self.error

    def closed_loop(self, backlog: int) -> None:
        with self._lock:
            self._mode, self._backlog = "closed", backlog

    def open_loop(self, t0: float, offsets: np.ndarray) -> None:
        with self._lock:
            self._mode = "open"
            self._due, self._due_next = t0 + offsets, 0

    def idle(self) -> None:
        """Stop creating and deleting; returns once the driver thread has
        seen it, so no pod is created after."""
        with self._lock:
            self._mode = "idle"
        self._in_driver(lambda: None)

    def burst(self, n: int) -> List[str]:
        """Create n pods at once (warm-up of one batch size)."""
        out: List[str] = []
        self._in_driver(lambda: out.extend(self._create(n, None)))
        return out

    def refresh(self, n: int, pause: Callable[[], None]) -> None:
        """Delete the oldest bound pods on n distinct nodes, `pause()`,
        then bind replacements on the same nodes: two batches that each
        meet about n changed nodes. The population is the same after."""
        gone: List[tuple] = []
        self._in_driver(lambda: gone.extend(self._delete_distinct(n)))
        pause()
        self._in_driver(lambda: self._put_back(gone))
        pause()

    def pending(self) -> int:
        """Pods created and not yet bound."""
        return len(self.created) - len(self.binds)

    def n_bound(self, keys) -> int:
        b = self.binds
        return sum(1 for k in keys if k in b)

    def _in_driver(self, fn: Callable[[], None]) -> None:
        done = threading.Event()

        def job():
            fn()
            done.set()

        with self._lock:
            self._todo.append(job)
        while not done.wait(0.05):
            if self.error is not None or not self._thread.is_alive():
                raise RuntimeError("traffic driver stopped") from self.error

    # ---- the driver thread ---------------------------------------------

    def _create(self, n: int, due) -> List[str]:
        t = self.template
        pods = [program_pod(t, f"{t['name_prefix']}{self._seq + i}")
                for i in range(n)]
        self._seq += n
        self.store.create_many(pods)
        now = time.time()
        keys = [p.key for p in pods]
        dues = due if due is not None else [now] * n
        for k, d in zip(keys, dues):
            self.created[k] = (now, d)
        return keys

    def _observe(self, evs) -> int:
        n = 0
        binds, fifo, node_of = self.binds, self.incoming, self.node_of
        for ev in evs:
            if ev.type != "MODIFIED":
                continue
            new, old = ev.object, ev.old_object
            if new.spec.node_name and not old.spec.node_name:
                key = new.key
                if key in binds:
                    self.rebinds += 1
                    continue
                binds[key] = (new.spec.node_name,
                              new.status.scheduled_time)
                node_of[key] = new.spec.node_name
                fifo.append(key)
                n += 1
        return n

    def _churn(self, n: int) -> None:
        store, fifo, deleted = self.store, self.incoming, self.deleted
        for _ in range(min(n, len(fifo))):
            key = fifo.popleft()
            store.delete("Pod", key)
            deleted[key] = time.time()

    def _delete_distinct(self, n: int) -> List[tuple]:
        seen, picked, rest = set(), [], []
        while self.fifo and len(picked) < n:
            key = self.fifo.popleft()
            node = self.node_of[key]
            (rest if node in seen else picked).append(key)
            seen.add(node)
        self.fifo.extendleft(reversed(rest))
        out = []
        for key in picked:
            self.store.delete("Pod", key)
            self.deleted[key] = time.time()
            out.append((key, self.node_of[key]))
        return out

    def _put_back(self, gone: List[tuple]) -> None:
        ts = [self.template_of[key] for key, _node in gone]
        pods = [to_program_pod(t, "put-back-" + key.split("/", 1)[1], node)
                for t, (key, node) in zip(ts, gone)]
        self.store.create_many(pods)
        now = time.time()
        for p, t in zip(pods, ts):
            self.put_back[p.key] = (p.spec.node_name, now)
            self.template_of[p.key] = t
            self.node_of[p.key] = p.spec.node_name
            self.fifo.append(p.key)

    def _run(self) -> None:
        try:
            self._loop()
        except BaseException as e:  # surfaced by stop() and _in_driver
            self.error = e

    def _loop(self) -> None:
        n_bound = 0
        while not self._stop.is_set():
            with self._lock:
                mode, backlog = self._mode, self._backlog
                due, i = self._due, self._due_next
                todo, self._todo = self._todo, []
            wait = 0.002
            if mode == "open" and due is not None and i < len(due):
                wait = min(0.005, max(0.0, due[i] - time.time()))
            n = self._observe(self._watch.next_events(65536, timeout=wait))
            n_bound += n
            if n and self.churn and mode != "idle":
                self._churn(n)
            if mode == "closed":
                want = backlog - (len(self.created) - n_bound)
                if want > 0:
                    self._create(want, None)
            elif mode == "open" and due is not None:
                now = time.time()
                j = int(np.searchsorted(due, now, side="right"))
                if j > i:
                    keys = self._create(j - i, due[i:j])
                    t_made = self.created[keys[0]][0]
                    self.late.extend((t_made - due[i:j]).tolist())
                    with self._lock:
                        if self._due is due:
                            self._due_next = j
            for job in todo:
                job()
