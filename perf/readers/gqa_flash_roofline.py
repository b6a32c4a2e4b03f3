"""The attention kernels' share of their roofline, %, for a family that
counts its own heads: the larger of their FLOPs over the chip's bf16 peak and
their least HBM bytes over its HBM peak (``perf/peaks.json``), over the own
device time of the Pallas kernels under the flax scope ``attention`` in the
traced window. The work is the family's ``flash_work`` (causal, forward and
backward, the model's query and K/V heads, every attention block) for the
traced steps' sequences: the reading ``perf/readers/mla_flash_roofline.py``
makes of the latent-attention kernels, which is one function for any family
that exports the count. A family that counts no such work, or a program
whose trace has no such kernel: ``None``."""

from perf.readers.mla_flash_roofline import read  # noqa: F401
