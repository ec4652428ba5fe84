"""Share of the traced window in which no operation ran on the device: one
minus the union of the operation intervals over the window, per device,
averaged over the cell's devices."""


def read(trace, record):
    devices = trace["devices"]
    if not devices or trace["window_s"] <= 0:
        return None
    idle = [1.0 - d["busy_s"] / trace["window_s"] for d in devices]
    return 100.0 * sum(idle) / len(idle)
