"""``step_mfu`` of a latent-attention MoE decoder: the FLOPs of
``flops_mla.py`` (absorbed latent attention in decode, the routed and
shared experts a token takes, the dense layers, the head) over the
device's busy seconds times its bf16 peak, as ``step_mfu.py`` reads it."""

from pathlib import Path

import flops_mla
from run import load_module

_base = load_module(Path(__file__).with_name("step_mfu.py"))
_base.flops = flops_mla
read = _base.read
