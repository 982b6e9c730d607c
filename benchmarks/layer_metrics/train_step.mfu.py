"""Model FLOP/s utilization: measured images/s/chip x the operations one
image needs in one BYOL step (benchmarks/lib/flops.py, from the
configuration's sizes) over the chip's published bf16 peak
(benchmarks/lib/peaks.py).  Absent off the chip."""
from benchmarks.lib import flops

NAME = "train_step.mfu"
LAYER = "train step"
UNIT = "%"
MOVES = "train_images_per_s_per_chip"
SOURCE = "host_clock"


def read(sources):
    rate = sources["counters"].get("train_images_per_s_per_chip")
    if rate is None or sources["peaks"] is None:
        return None
    conf = sources["config"]
    per_image = flops.train_flops_per_image(
        conf["arch"], conf["image_size"],
        head_hidden=conf["head_latent_size"],
        projection=conf["projection_size"])
    return 100.0 * rate * per_image / sources["peaks"]["bf16_flops_per_s"]
