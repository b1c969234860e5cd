"""Assigned architecture configs (``--arch <id>``).  Importing this package
populates the registry."""
from repro.configs import (  # noqa: F401
    deepseek_v2_lite,
    granite_3_8b,
    hubert_xlarge,
    hymba_1_5b,
    llava_next_34b,
    nemotron_4_15b,
    olmoe_1b_7b,
    paper_mt_base,
    qwen2_moe_a2_7b,
    rwkv6_1_6b,
    stablelm_12b,
    starcoder2_7b,
)

ASSIGNED = [
    "hymba-1.5b",
    "llava-next-34b",
    "qwen2-moe-a2.7b",
    "stablelm-12b",
    "rwkv6-1.6b",
    "starcoder2-7b",
    "hubert-xlarge",
    "nemotron-4-15b",
    "olmoe-1b-7b",
    "granite-3-8b",
    "deepseek-v2-lite",
]
