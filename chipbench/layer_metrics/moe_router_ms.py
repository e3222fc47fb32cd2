"""Reads ``moe_router_ms`` as ``moe_router_ms.json`` beside this file says
(``chipbench/trace_stats.py`` ``read_spec``)."""

from chipbench import trace_stats

read = trace_stats.reader(__file__)
