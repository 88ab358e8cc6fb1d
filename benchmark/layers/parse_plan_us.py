"""Parse / plan: `parse` + `plan` net of the index search that a KNN plan
runs eagerly inside it (`idx/planner.py plan_scan`: `plan` CONTAINS
`index_knn`), per request. Opening the transaction is `txn_open_us`. A
statement whose text the datastore's AST cache holds records no `parse`:
with the vector bound as `$q` that is every request of the window."""


def read(window):
    st = window["stages"]
    if "plan" not in st or not window["requests"]:
        return None
    total = st.get("parse", {"total_us": 0.0})["total_us"] \
        + st["plan"]["total_us"] \
        - st.get("index_knn", {"total_us": 0.0})["total_us"]
    return total / window["requests"]
