"""TensorFlow for the HOST input pipeline only.

tf.data decodes and augments on the host's CPU cores in the same process
that owns the chip through JAX, so TensorFlow must see no accelerator:
every module of this package takes ``tf`` from here.
"""
import tensorflow as tf

for _kind in ("GPU", "TPU"):
    try:
        tf.config.set_visible_devices([], _kind)
    except (RuntimeError, ValueError):
        pass    # TensorFlow's runtime was initialised before this import
