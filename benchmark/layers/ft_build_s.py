"""Index engine: seconds set-up spent making the full-text index's device
copy: stage `ft_build` (`idx/ftdevice.py _snapshot`: the `bl`, `bf` and `bs`
keys read from KV truth, the postings decoded natively, the CSR arrays
built and shipped by `ft_load`), summed over set-up's builds. Read from
`window["before"]`, the process's totals at the window's start, which are
set-up's (a read-only window builds nothing). A program without the stage
reads nothing."""


def read(window):
    st = window["before"].get("stages", {}).get("ft_build")
    return st["total_ms"] / 1e3 if st else None
