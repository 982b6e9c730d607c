"""Imbalance of the held experts: the largest load over the mean load, each
summed over the layers that route, median over the window's steps (the
step's routing counters).  1 is a perfectly even split; the ragged products
take the time of the rows they get, so this moves the step only through
tile padding."""
import statistics

NAME = "moe.load_max_over_mean"
LAYER = "train step"
UNIT = "ratio"
MOVES = "train_images_per_s_per_chip"
SOURCE = "program_counter"


def read(sources):
    top = sources["counters"].get("moe_load_max")
    mean = sources["counters"].get("moe_load_mean")
    if not top or not mean:
        return None
    return statistics.median(a / b for a, b in zip(top, mean) if b > 0)
