"""Moonlight-16B-A3B as one chip of eight holds it (the model-configs
guide's cut): each layer is shared by 8 chips, attention and the shared
experts data-parallel, the routed experts expert-parallel (experts 0-7 of
64 here), the vocabulary split 8 ways (rows 0-20479 of 163840).  The
dense layer and 5 MoE layers; every width, the router's 64 outputs, its
top-6 and the scaling factor as published.
"""
import dataclasses

from repro.configs import moonlight_16b_a3b
from repro.models.config import ModelConfig

CONFIG = dataclasses.replace(
    moonlight_16b_a3b.CONFIG, name="moonlight-16b-a3b-ep8", num_layers=6,
    experts_held=8, vocab_size=20480)


def tiny() -> ModelConfig:
    return dataclasses.replace(moonlight_16b_a3b.tiny(),
                               name="moonlight-tiny-ep8", experts_held=2)
