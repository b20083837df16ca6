"""The device's idle share of the traced window, in percent."""


def read(select: dict, record: dict):
    trace = record.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
