"""Share of the profiled campaign in which no operation ran on the device
(the union of the trace's device-op intervals against the campaign's
span on the host)."""


def read(data):
    if data["traced_s"] <= 0 or not data["device_ops"]:
        return None
    return 100.0 * (1.0 - data["busy_s"] / data["traced_s"])
