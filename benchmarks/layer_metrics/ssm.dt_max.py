"""The largest step ``delta`` (after its softplus) of the selective scan in
the online forward, the median over the window's steps: what decides
whether a state forgets (the step's ``_ssm_dt_max``, sown by the layer)."""
from benchmarks.lib import trace_sambay_trunk

NAME = "ssm.dt_max"
LAYER = "train step"
UNIT = "step"
MOVES = "train_images_per_s_per_chip"
SOURCE = "program_counter"


def read(sources):
    return trace_sambay_trunk.counter_median(sources, "ssm_dt_max")
