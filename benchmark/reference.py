"""The plain reference: what a client may conclude from its own watch.

Imports nothing of the program.  It is given what the benchmark itself
made from the seed (node shapes, pod requests) and what the client saw on
its store watch, in the store's order, and replays it: every pod offered
has to be bound exactly once, to a node of the deployment that passes
every filter the deployment's nodes and pods can fail — it is not
cordoned (NodeUnschedulable) and it never holds more than its allocatable
cpu, memory or pod count (NodeResourcesFit); nodes carry no taints and
pods no selectors.  The occupancy it arrives at is what the device's
table has to hold, row for row.
"""

from __future__ import annotations

import numpy as np

BIND_MARK = b'"nodeName":"'


def bind_node(val: bytes) -> bytes | None:
    """The node a pod object is bound to, or None if it is pending."""
    i = val.find(BIND_MARK)
    if i < 0:
        return None
    j = i + len(BIND_MARK)
    return val[j:val.index(b'"', j)]


class Ledger:
    """Watch events as the client drained them: ``(t, batch)`` with the
    store's columnar pod events (``etype`` 0 = put, 1 = delete; ``flags``
    bit 1 = the value parsed natively, bit 2 = it holds a nodeName;
    ``koff``/``key_blob`` the keys; ``aoff``/``aux_blob`` the node name
    of a natively parsed bound pod, or the whole value of any other)."""

    def __init__(self, key_prefix_len: int) -> None:
        self._plen = key_prefix_len
        self.batches: list = []

    def add(self, t: float, batch) -> None:
        if batch.n:
            self.batches.append((t, batch))

    def arrays(self, node_index: dict[bytes, int]) -> dict:
        """Binds as arrays, in the order the watch gave them.  A bind to
        a name outside the deployment gets node -1."""
        plen = self._plen
        bp, bn, bt, deleted = [], [], [], 0
        for t, ev in self.batches:
            koff, aoff = ev.koff.tolist(), ev.aoff.tolist()
            keys, aux = ev.key_blob, ev.aux_blob
            etype, flags = ev.etype.tolist(), ev.flags.tolist()
            for j in range(ev.n):
                if etype[j] != 0:
                    deleted += 1
                    continue
                a = aux[aoff[j]:aoff[j + 1]]
                if flags[j] & 1:
                    node = a if flags[j] & 2 else None
                else:
                    node = bind_node(a)
                if node is not None:
                    bp.append(int(keys[koff[j] + plen:koff[j + 1]]))
                    bn.append(node_index.get(node, -1))
                    bt.append(t)
        return {
            "bind_pod": np.asarray(bp, np.int64),
            "bind_node": np.asarray(bn, np.int64),
            "bind_t": np.asarray(bt, np.float64),
            "deleted": deleted,
        }


def window_rate(bind_t: np.ndarray, t0: float, t1: float) -> tuple[int, float]:
    """Binds the client saw in [t0, t1] and their rate over the whole
    window — all the work over all the time."""
    n = int(((bind_t >= t0) & (bind_t <= t1)).sum())
    return n, n / (t1 - t0)


def replay(seen: dict, *, offered: int, pod_cpu: np.ndarray,
           pod_mem: np.ndarray, alloc_cpu: np.ndarray, alloc_mem: np.ndarray,
           alloc_pods: np.ndarray, cordoned: np.ndarray) -> dict:
    """Hold the watch's history to the deployment's guarantees.

    Nothing is ever deleted, so a node's occupancy only rises and its
    final value is its peak.  Returns the numbers compared (each has the
    limit 0) and the final per-node occupancy."""
    n_nodes = alloc_cpu.size
    bp, bn = seen["bind_pod"], seen["bind_node"]
    per_pod = np.bincount(bp[bp < offered], minlength=offered)
    known = bn >= 0
    node_of_pod = np.full(offered, -1, np.int64)
    # the first bind decides where a pod is; a second is counted above
    first = np.unique(bp[known], return_index=True)[1]
    pods, node = bp[known][first], bn[known][first]
    node_of_pod[pods] = node
    final = {
        "cpu": np.bincount(node, pod_cpu[pods], n_nodes).astype(np.int64),
        "mem": np.bincount(node, pod_mem[pods], n_nodes).astype(np.int64),
        "pods": np.bincount(node, minlength=n_nodes).astype(np.int64),
    }
    over = ((final["cpu"] > alloc_cpu) | (final["mem"] > alloc_mem)
            | (final["pods"] > alloc_pods))
    return {
        "numbers": {
            "never_bound": int((per_pod == 0).sum()),
            "bound_twice": int((per_pod > 1).sum()),
            "unknown_node": int((~known).sum()),
            "bound_to_cordoned": int(cordoned[node].sum()),
            "overcommitted_nodes": int(over.sum()),
            "deleted": int(seen["deleted"]),
        },
        "node_of_pod": node_of_pod,
        "final_cpu": final["cpu"],
        "final_mem": final["mem"],
        "final_pods": final["pods"],
    }


def rows_wrong(final: dict, row_of_node: np.ndarray, dev_cpu: np.ndarray,
               dev_mem: np.ndarray, dev_pods: np.ndarray) -> int:
    """Rows of the device's table whose requested columns differ from the
    replayed occupancy (rows that hold no node have to read 0)."""
    want = np.zeros((3, dev_cpu.size), np.int64)
    want[0, row_of_node] = final["final_cpu"]
    want[1, row_of_node] = final["final_mem"]
    want[2, row_of_node] = final["final_pods"]
    got = np.stack([dev_cpu, dev_mem, dev_pods]).astype(np.int64)
    return int((want != got).any(axis=0).sum())
