"""Reads ``short_conv_hbm_pct`` as ``short_conv_hbm_pct.json`` beside this
file says (``chipbench/scope_roofline.py``). The bytes are the program's
``short_conv_bytes`` stat on ``serve/launch``; ``short_conv_bytes`` below is
the function they follow, whatever implements the operator.
"""

import functools
import json

from chipbench import scope_roofline

with open(__file__[:-3] + ".json") as _f:
    SPEC = json.load(_f)


def short_conv_bytes(conv_layers: int, hidden: int, reach: int,
                     itemsize: int, live_slots: int, steps: int = 1) -> int:
    """Bytes a launch of ``steps`` decode steps must move for its gated
    short convolutions: each conv layer's in projection (hidden x 3 hidden),
    out projection (hidden x hidden) and taps (hidden x reach), streamed
    once a step, and the ``reach`` columns of state of every live slot and
    conv layer. At the published sizes 8 x 16.78M x 2 B = 268.5 MB of
    weights and 98,304 B a live slot: 293.7 MB a step at 256."""
    weights = conv_layers * (hidden * 3 * hidden + hidden * hidden
                             + hidden * reach) * itemsize
    state = live_slots * conv_layers * reach * hidden * itemsize
    return (weights + state) * steps


read = functools.partial(scope_roofline.read, SPEC)
