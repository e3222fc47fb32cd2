"""Reads ``lfm2_expert_hbm_pct`` as ``lfm2_expert_hbm_pct.json`` beside this
file says (``chipbench/scope_roofline.py``). The bytes are the program's
``expert_weight_bytes`` stat on ``serve/launch``; they follow
``expert_bytes`` of ``moe_expert_hbm_pct.py`` (held experts x sparse layers
x 3 x hidden x width x itemsize x steps), whatever implements the products.
"""

import functools
import json

from chipbench import scope_roofline

with open(__file__[:-3] + ".json") as _f:
    SPEC = json.load(_f)

read = functools.partial(scope_roofline.read, SPEC)
