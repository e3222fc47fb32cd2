"""Reads ``swa_attention_hbm_pct`` as ``swa_attention_hbm_pct.json`` beside this file says
(``chipbench/trace_stats.py`` ``read_spec``)."""

from chipbench import trace_stats

read = trace_stats.reader(__file__)
