"""A formula over the engine's counters (deltas over the window) and
the engine's arguments, e.g. ``100 * decode_tokens / (decode_steps *
max_batch_size)``."""


def read(metric: dict, run: dict):
    try:
        return float(eval(metric["formula"], {"__builtins__": {}},
                          dict(run["counters"])))
    except (NameError, ZeroDivisionError):
        return None
