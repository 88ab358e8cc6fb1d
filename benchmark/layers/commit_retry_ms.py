"""Executor: what the attempts that lost their commit cost, per request:
stage `commit_retry` (`exec/executor.py`: an auto-commit write whose commit
lost to a concurrent writer of the same index is run again inside the
server; each thrown-away attempt's wall time). Only a cell that writes
records it. A window with no lost commit reads 0.0 where the program has
recorded the stage before it (warm-up drives the same writers), nothing
where it never has."""


def read(window):
    if not window["requests"]:
        return None
    st = window["stages"].get("commit_retry")
    if not st:
        seen = "commit_retry" in window["after"]["stages"]
        return 0.0 if seen else None
    return st["total_us"] / window["requests"] / 1e3
