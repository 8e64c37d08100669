"""Device milliseconds per edge round in attention: the self time of the
operations under the program's scopes ``attention`` and
``cross_attention``, forward and backward, over the traced rounds (the
busiest device; ``bench/kinds/edge_round_layers.py``)."""


def read(out):
    secs = getattr(out, "attention_s", None)
    if secs is None or not out.rounds_traced:
        return None
    return 1e3 * secs / out.rounds_traced
