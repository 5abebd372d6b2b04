"""EV01 corpus: raw environment reads of package knobs."""
import os

MATMUL = os.environ.get("MXTPU_FP32_MATMUL", "strict")
DEBUG = os.getenv("MXNET_DEBUG_FLAG")
HOME = os.environ["MXNET_HOME"]
