"""Index engine: what the full-text batcher's dispatcher does on the host
once `ft_bm25` has returned, per dispatch: stage `ft_post`
(`idx/ftdevice.py _dispatch`): every rider's candidates rescored in f64 from
their tf and dl, sorted, cut to n and checked (a rider sent round again on
a larger rung has its second RPC inside the dispatch, not inside the
stage). A program without the stage reads nothing."""


def read(window):
    st = window["stages"].get("ft_post")
    if not st or not st["count"]:
        return None
    return st["total_us"] / st["count"] / 1e3
