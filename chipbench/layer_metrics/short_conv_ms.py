"""Reads ``short_conv_ms`` as ``short_conv_ms.json`` beside this file says
(``chipbench/trace_stats.py`` ``read_spec``)."""

from chipbench import trace_stats

read = trace_stats.reader(__file__)
