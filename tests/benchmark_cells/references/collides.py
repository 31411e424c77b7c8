"""A toy reference that returns a number the base comparison owns."""


def numbers(seen, replayed, *, nodes, pattern, offered):
    return {"bound_to_odd_node": 0, "device_rows_wrong": 0}
