"""The third cell's own guarantees (tests/benchmark_cells/third_cell): two
numbers beside the base replay's twelve, both 0 in every sound run.
Imports nothing: what it is given is all it has."""


def numbers(seen, replayed, *, nodes, pattern, offered):
    node = replayed["node_of_pod"]
    assert len(node) == offered and len(pattern) == 4
    # in the client's watch order, as a skew would be counted: a bind to a
    # node that already held its pod count when the client saw it
    held, late = {}, 0
    for n in seen["bind_node"].tolist():
        late += held.get(n, 0) >= nodes["pods"]
        held[n] = held.get(n, 0) + 1
    return {"bound_past_the_last_node": int((node >= nodes["count"]).sum()),
            "bound_to_a_full_node": int(late)}
