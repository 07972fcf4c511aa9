"""Share of the traced serving window in which no op ran on the device."""
import tracing


def read(L):
    if L.window_s <= 0 or not tracing.device_ops(L.events):
        return None
    return 100.0 * (1.0 - tracing.busy_s(L.events) / L.window_s)
