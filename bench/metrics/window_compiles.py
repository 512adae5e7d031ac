"""Compile layer: backend compiles that ran inside the measured window
(JAX's monitoring events, loads from the persistent cache left out)."""


def read(rec):
    return rec["compiled_in_window"]
