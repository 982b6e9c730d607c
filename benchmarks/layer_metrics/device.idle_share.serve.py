"""Share of the traced window in which no operation ran on the device
(mean over the chips used), serving cells."""
NAME = "device.idle_share.serve"
LAYER = "device"
UNIT = "%"
MOVES = "serve_images_per_s"
SOURCE = "device_trace"


def read(sources):
    trace = sources["trace"]
    if trace is None or "serve_images_per_s" not in sources["counters"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
