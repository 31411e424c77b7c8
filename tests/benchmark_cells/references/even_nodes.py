"""A toy reference for the tests: a deployment that "guarantees" no pod
lands on an even-numbered node, which no sound run keeps.  Imports
nothing, as a real one under benchmark/references/ imports nothing."""


def numbers(seen, replayed, *, nodes, pattern, offered):
    node = replayed["node_of_pod"]
    assert len(node) == offered and nodes["count"] > 0 and pattern
    return {"bound_to_even_node": int(((node >= 0) & (node % 2 == 0)).sum())}
