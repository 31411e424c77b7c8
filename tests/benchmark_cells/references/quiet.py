"""A toy reference whose guarantee every sound run keeps."""


def numbers(seen, replayed, *, nodes, pattern, offered):
    return {"bound_past_the_last_node": int(
        (replayed["node_of_pod"] >= nodes["count"]).sum())}
