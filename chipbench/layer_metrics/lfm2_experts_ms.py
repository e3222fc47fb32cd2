"""Reads ``lfm2_experts_ms`` as ``lfm2_experts_ms.json`` beside this file says
(``chipbench/trace_stats.py`` ``read_spec``)."""

from chipbench import trace_stats

read = trace_stats.reader(__file__)
