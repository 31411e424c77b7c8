"""A toy control for the tests, as a real one under benchmark/controls/
is written: every pod of a wave is bound to the node the device chose
for the wave's first pod, which no node's pod count holds.  It patches
the store's class and returns the call that undoes that."""


def plant(store, coord):
    from k8s1m_tpu.store.native import MemStore

    real = MemStore.bind_batch

    def bind_batch(self, binds, *rest):
        binds = list(binds)
        return real(self, [(key, mod, binds[0][2]) for key, mod, _ in binds], *rest)

    MemStore.bind_batch = bind_batch

    def undo():
        MemStore.bind_batch = real

    return undo
