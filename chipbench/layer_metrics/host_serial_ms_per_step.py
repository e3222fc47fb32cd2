"""Reads ``host_serial_ms_per_step`` as ``host_serial_ms_per_step.json`` beside this file says
(``chipbench/trace_stats.py`` ``read_spec``)."""

from chipbench import trace_stats

read = trace_stats.reader(__file__)
