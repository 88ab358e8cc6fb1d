"""Index engine: wall time of a request's BM25 leg: stage `ft_search`
(`idx/ftdevice.py device_leg`, or `idx/fulltext.py ft_result` where the host
answers), from the query's analysis until its rows are known, per request.
It holds the full-text batcher's wait and ride (`batch_wait_us`,
`batch_ride_us`), and in the ride the RPC, the program `_bm25_impl` and the
f64 rescore. A program without the stage reads nothing."""


def read(window):
    st = window["stages"].get("ft_search")
    if not st or not window["requests"]:
        return None
    return st["total_us"] / window["requests"]
