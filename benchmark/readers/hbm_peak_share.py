"""``peak_bytes_in_use`` over ``bytes_limit`` on the fullest chip, %, read
when the window closes and before the reference runs."""


def read(r, args):
    if not r.device.get("memory_peak_bytes") or not r.device.get(
            "memory_limit_bytes"):
        return None
    return 100.0 * r.device["memory_peak_bytes"] / r.device[
        "memory_limit_bytes"]
