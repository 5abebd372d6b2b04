"""EV01 corpus (clean): reads go through the declared helpers; non-package
variables may stay raw."""
import os

from util import getenv_str

MATMUL = getenv_str("MXTPU_FP32_MATMUL")
PLATFORM = os.environ.get("JAX_PLATFORMS")  # not an MXNET_/MXTPU_ knob
