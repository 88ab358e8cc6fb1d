"""Runner: first-shape dispatches inside the window, whether compiled or
loaded from the persistent cache. Warm-up is there so that this reads 0."""


def read(window):
    before, after = window["before"]["runner"], window["after"]["runner"]
    return after["cc"]["misses"] - before["cc"]["misses"]
