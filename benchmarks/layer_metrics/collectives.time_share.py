"""Share of the traced window that device 0 spent in collective
operations (all-reduce, all-gather, reduce-scatter, collective-permute,
all-to-all under XLA's own names).  Only a cell on several chips has any."""
NAME = "collectives.time_share"
LAYER = "collectives"
UNIT = "%"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    trace = sources["trace"]
    if trace is None or sources["counters"].get("chips", 1) < 2:
        return None
    if "train_images_per_s_per_chip" not in sources["counters"]:
        return None
    return 100.0 * trace["collective_s"] / trace["window_s"]
