"""Runner: the runner's own host work in one device RPC, mean over the
window's calls: stages `runner_h2d` (the `jnp.asarray` of the queries),
`runner_d2h` (the outputs after the first copied back into numpy) and
`runner_other` (everything else between the request read and the reply
sent: the store lookup, padding on the host, the reply's meta)."""

PARTS = ("runner_h2d", "runner_d2h", "runner_other")


def read(window):
    stages = window["stages"]
    st = stages.get("runner_other")
    if not st or not st["count"]:
        return None
    return sum(stages.get(p, {}).get("total_us", 0.0) for p in PARTS) \
        / st["count"] / 1e3
