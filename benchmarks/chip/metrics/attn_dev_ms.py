"""Attention (the paged K/V gather, scores and softmax): device ms per
execution of the decode program in ops traced under the model's
``attention`` scope (``trace_scopes.py``)."""


def read(run):
    decode = (run.trace or {}).get("scopes", {}).get("decode", {})
    return 1e3 * decode["attention"] if "attention" in decode else None
