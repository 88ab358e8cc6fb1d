"""Executor: statement machinery and evaluation, net of what is nested in
it, per request. `stmt_envelope` is already net of `stmt_eval` but holds
`txn_open` (`exec/executor.py`: the envelope's clock starts before the
transaction opens); `stmt_eval` holds `plan`, which holds `index_knn`."""


def read(window):
    st = window["stages"]
    if "stmt_eval" not in st or "stmt_envelope" not in st \
            or not window["requests"]:
        return None
    total = st["stmt_envelope"]["total_us"] + st["stmt_eval"]["total_us"] \
        - st.get("txn_open", {"total_us": 0.0})["total_us"] \
        - st.get("plan", {"total_us": 0.0})["total_us"]
    return total / window["requests"]
