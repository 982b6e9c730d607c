"""Share of the traced window in which no operation ran on the device
(mean over the chips used), training cells."""
NAME = "device.idle_share.train"
LAYER = "device"
UNIT = "%"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    trace = sources["trace"]
    if trace is None or \
            "train_images_per_s_per_chip" not in sources["counters"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
