"""The third cell's control (door 5): every pod of a wave is bound to the
node the device chose for the next pod of that wave.  Each node still
gets one pod of the wave, so a mix of one size keeps every count right;
with two sizes the requested cpu of the host mirror and of the device
table no longer is what the client saw bound."""


def plant(store, coord):
    from k8s1m_tpu.store.native import MemStore

    real = MemStore.bind_batch

    def bind_batch(self, binds, *rest):
        binds = list(binds)
        nodes = [b[2] for b in binds]
        turned = [(key, mod, node) for (key, mod, _), node
                  in zip(binds, nodes[1:] + nodes[:1])]
        return real(self, turned, *rest)

    MemStore.bind_batch = bind_batch

    def undo():
        MemStore.bind_batch = real

    return undo
