"""Traffic generation: nodes and pods, from data.

One general generator.  A deployment's nodes come from the ``nodes``
object of its configuration file, a cell's pods from
``benchmark/pods/<name>.json`` (a weighted list of shapes), its arrivals
from ``benchmark/workloads/<name>.json``.  Objects are the wire format the
program's own tools write (``encode_node(build_node(i))``,
``encode_pod(build_pod(i))``: the repo's ports of upstream's make_nodes
and make_pods), produced from pre-encoded templates so that the
generator's cost per wave stays small; ``verify`` holds the templates to
the program's encoders on a sample.
"""

from __future__ import annotations

import inspect
import math
import random
import struct

PODS_PREFIX = b"/registry/pods/"
NODES_PREFIX = b"/registry/minions/"
_PLACEHOLDER = 987654321987654321     # a pod index no run reaches


def builder_keywords(builder, given: dict, what: str) -> dict:
    """The keyword defaults of the program's ``builder`` (``build_pod``,
    ``build_node``), after holding ``given`` to them: a key the builder
    does not take fails here, by name, and not as a pod or node quietly
    built without it."""
    takes = {
        name: p.default
        for name, p in inspect.signature(builder).parameters.items()
        if p.kind is p.KEYWORD_ONLY
    }
    for key in given:
        if key not in takes:
            raise ValueError(
                f"{what}: key {key!r} is no keyword of "
                f"{builder.__module__}.{builder.__name__} "
                f"(it takes {sorted(takes)})"
            )
    return takes


class Nodes:
    """``count`` KWOK nodes of one shape; labels cycle with the index.
    With ``cordon_every`` = n, the last node of every n is cordoned
    (``spec.unschedulable``): no pod may be bound to it.  Every other key
    of the ``nodes`` object is a keyword of ``make_nodes.build_node``."""

    def __init__(self, shape: dict) -> None:
        from k8s1m_tpu.control.objects import encode_node
        from k8s1m_tpu.tools.make_nodes import KWOK_GROUPS, build_node

        self.count = int(shape["count"])
        self.cordon_every = int(shape.get("cordon_every", 0))
        self._kw = {k: v for k, v in shape.items()
                    if k not in ("count", "cordon_every")}
        kw = {**builder_keywords(build_node, self._kw, "nodes"), **self._kw}
        self.prefix = kw["prefix"]
        self.cpu_milli = int(kw["cpu_milli"])
        self.mem_kib = int(kw["mem_kib"])
        self.pods = int(kw["pods"])
        period = math.lcm(KWOK_GROUPS, int(kw["zones"]), int(kw["regions"]),
                          self.cordon_every or 1)
        self._tmpl = []
        for j in range(period):
            head, name, tail = encode_node(self.build(j)).partition(
                f'"{self.prefix}-{j}"'.encode()
            )
            if not name or name in tail:
                raise RuntimeError("node template: name not found exactly once")
            self._tmpl.append((head + b'"', b'"' + tail))

    def cordoned(self, i: int) -> bool:
        n = self.cordon_every
        return bool(n) and i % n == n - 1

    def build(self, i: int):
        from k8s1m_tpu.tools.make_nodes import build_node

        node = build_node(i, **self._kw)
        node.unschedulable = self.cordoned(i)
        return node

    def name(self, i: int) -> bytes:
        return f"{self.prefix}-{i}".encode()

    def items(self, lo: int, hi: int) -> list[tuple[bytes, bytes]]:
        tmpl, period = self._tmpl, len(self._tmpl)
        out = []
        for i in range(lo, hi):
            name = self.name(i)
            head, tail = tmpl[i % period]
            out.append((NODES_PREFIX + name, head + name + tail))
        return out

    def verify(self, rng: random.Random, samples: int = 64) -> None:
        from k8s1m_tpu.control.objects import encode_node, node_key

        for i in [0, self.count - 1] + [
            rng.randrange(self.count) for _ in range(samples)
        ]:
            want = (node_key(f"{self.prefix}-{i}"), encode_node(self.build(i)))
            if self.items(i, i + 1)[0] != want:
                raise RuntimeError(f"node template differs from encode_node at {i}")


def load_nodes(store, nodes: Nodes) -> None:
    for lo in range(0, nodes.count, 8192):
        store.put_batch(nodes.items(lo, min(lo + 8192, nodes.count)))


def shape_pattern(params: dict, seed: int) -> list[dict]:
    """The cell's pod shapes, each repeated by its weight, in an order
    drawn from the seed: every seed offers the same set, in another
    order.  Pod ``i`` has shape ``pattern[i % len(pattern)]``.  A shape
    is every key of its entry but ``weight``: keywords of
    ``make_pods.build_pod``."""
    pattern = [
        {k: v for k, v in s.items() if k != "weight"}
        for s in params["shapes"] for _ in range(int(s.get("weight", 1)))
    ]
    random.Random(seed).shuffle(pattern)
    return pattern


class Pods:
    """Pod ``i`` of a run, as ``make_pods.build_pod`` makes it (the app
    label and the kwok toleration with it): key
    ``/registry/pods/b<seed>/<prefix>-<i>``."""

    def __init__(self, params: dict, seed: int) -> None:
        from k8s1m_tpu.control.objects import encode_pod
        from k8s1m_tpu.tools.make_pods import build_pod

        self.namespace = f"b{seed}"
        self.pattern = shape_pattern(params, seed)
        given = {k: None for shape in self.pattern for k in shape}
        self._defaults = builder_keywords(build_pod, given, "pods shape")
        for fixed in ("prefix", "namespace"):      # the keys are made of them
            if fixed in given:
                raise ValueError(f"pods shape: key {fixed!r} is the generator's")
        self.prefix = self._defaults["prefix"]      # make_pods' own
        self._rec = struct.Struct("<II").pack     # key length, value length
        stem = f"{self.prefix}-".encode()
        self.key_prefix = PODS_PREFIX + f"{self.namespace}/".encode() + stem
        self._tmpl = []
        for shape in self.pattern:
            head, name, tail = encode_pod(
                self.build(_PLACEHOLDER, shape)
            ).partition(stem + str(_PLACEHOLDER).encode())
            if not name or str(_PLACEHOLDER).encode() in tail:
                raise RuntimeError("pod template: name not found exactly once")
            self._tmpl.append((head + stem, tail))

    def build(self, i: int, shape: dict):
        from k8s1m_tpu.tools.make_pods import build_pod

        return build_pod(i, namespace=self.namespace, **shape)

    def key(self, i: int) -> bytes:
        return self.key_prefix + str(i).encode()

    def requests(self, key: str) -> list[int]:
        """``cpu_milli`` or ``mem_kib`` of each shape of the pattern, with
        ``build_pod``'s default where a shape leaves it out."""
        return [int(s.get(key, self._defaults[key])) for s in self.pattern]

    def wave(self, lo: int, n: int) -> list[tuple[bytes, bytes]]:
        tmpl, period, kp = self._tmpl, len(self._tmpl), self.key_prefix
        out = []
        for i in range(lo, lo + n):
            s = str(i).encode()
            head, tail = tmpl[i % period]
            out.append((kp + s, head + s + tail))
        return out

    def frame(self, lo: int, n: int) -> bytes:
        """``wave(lo, n)`` packed as the store's put frame (what
        ``pack_put_frame`` makes of it): per pod a record of key length
        and value length, the key, the value."""
        tmpl, period, kp = self._tmpl, len(self._tmpl), self.key_prefix
        rec = self._rec
        parts = []
        for i in range(lo, lo + n):
            s = b"%d" % i
            head, tail = tmpl[i % period]
            parts += (rec(len(kp) + len(s), len(head) + len(s) + len(tail)),
                      kp, s, head, s, tail)
        return b"".join(parts)

    def verify(self, rng: random.Random, samples: int = 64) -> None:
        from k8s1m_tpu.control.objects import encode_pod, pod_key
        from k8s1m_tpu.store.native import pack_put_frame

        for i in [0] + [rng.randrange(1 << 24) for _ in range(samples)]:
            pod = self.build(i, self.pattern[i % len(self.pattern)])
            want = (pod_key(pod.namespace, pod.name), encode_pod(pod))
            if self.wave(i, 1)[0] != want:
                raise RuntimeError(f"pod template differs from encode_pod at {i}")
            if self.frame(i, 3) != pack_put_frame(self.wave(i, 3)):
                raise RuntimeError(f"pod frame differs from pack_put_frame at {i}")
