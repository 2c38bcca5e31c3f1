"""Peak device memory of the fullest chip as a share of what the
backend lets a process use (``memory_stats()``)."""


def read(metric: dict, run: dict):
    memory = run["memory"]
    if not memory.get("peak_bytes_in_use") or not memory.get("bytes_limit"):
        return None
    return 100.0 * memory["peak_bytes_in_use"] / memory["bytes_limit"]
