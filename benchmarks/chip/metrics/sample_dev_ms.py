"""Sampling tail: device ms per execution of the decode program in ops
traced under the model's ``sample`` scope (``trace_scopes.py``)."""


def read(run):
    decode = (run.trace or {}).get("scopes", {}).get("decode", {})
    return 1e3 * decode["sample"] if "sample" in decode else None
