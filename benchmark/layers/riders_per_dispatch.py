"""Batcher: queries that rode one device dispatch, over the window."""


def read(window):
    b = window["batching"]
    if not b["dispatches"]:
        return None
    return b["riders"] / b["dispatches"]
