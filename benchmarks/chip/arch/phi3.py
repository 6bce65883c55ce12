"""``model_type: phi3``: the dense decoder (``arch/dense.py``)."""

from arch.dense import KERNELS, decode_flops, model_config_kwargs  # noqa: F401
from arch.dense import numbers, prefill_flops, served_gaps, weights  # noqa: F401
