"""Graph engine: wall time of one folded `->edge->node` chain inside the
seam, per request: stage `graph_hop` (`exec/eval.py _csr_bag_pair_hop`):
start keys to node indexes, the batcher's wait and ride (`batch_wait_us`,
`batch_ride_us`), and the node indexes back to record ids
(`graph/csr.py hop_bag_served`); on a program that walks its host CSR
instead, that walk."""


def read(window):
    st = window["stages"].get("graph_hop")
    if not st or not window["requests"]:
        return None
    return st["total_us"] / window["requests"]
