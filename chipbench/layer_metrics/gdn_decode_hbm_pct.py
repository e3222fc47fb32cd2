"""Reads ``gdn_decode_hbm_pct`` as ``gdn_decode_hbm_pct.json`` beside this
file says (``chipbench/trace_stats.py`` ``read_spec``). The bytes are the
program's ``delta_state_bytes`` stat on ``serve/launch``;
``delta_state_bytes`` below is the function they follow, whatever
implements the recurrence.
"""

from chipbench import trace_stats


def delta_state_bytes(live_slots: int, linear_layers: int, heads: int,
                      key_head: int, value_head: int, steps: int = 1) -> int:
    """Bytes a launch of ``steps`` decode steps must move for the delta
    rule: every live slot's float32 state (heads x key head x value head a
    linear layer), read once and written once a step. 6 x 30 x 96 x 192 x
    4 B = 13,271,040 B a slot, 26.5 MB moved a slot a step, 6.79 GB at 256
    slots."""
    return 2 * live_slots * linear_layers * heads * key_head * value_head \
        * 4 * steps


read = trace_stats.reader(__file__)
