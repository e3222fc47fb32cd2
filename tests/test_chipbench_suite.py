"""The benchmark's own tests, collected by ``pytest tests/``.

``chipbench/test_*.py`` check that the metrics' readers still match the
names the program emits (kernel ``name=``, ``named_scope``, ``serve/*``
spans); tier-1 is ``pytest tests/``, so they are imported here, not copied.
Each function keeps its own module's globals and helpers; a module-scoped
fixture (``kexaone``, ``lfm2``, ``joyai_config``, ``olmo_config``,
``olmo_launches``) comes along by name; no
two of the modules give a test the same name.

One test is collected in a wrapper: ``test_lfm2_new_cell_reports_what_it_must``
pins LFM2's entries to the END of ``configs`` and ``per_layer``, which held
only until a later configuration appended its own. The wrapper runs it, as
written, on the lists as LFM2 left them, and checks that whatever follows
LFM2's entries was appended after them.
"""

import copy

from chipbench import test_serve_lfm2 as _lfm2
from chipbench.test_chipbench import *  # noqa: F401,F403
from chipbench.test_serve_family import *  # noqa: F401,F403
from chipbench.test_serve_joyai import *  # noqa: F401,F403
from chipbench.test_serve_lfm2 import *  # noqa: F401,F403
from chipbench.test_serve_olmo_hybrid import *  # noqa: F401,F403
from chipbench.test_trace_stats import *  # noqa: F401,F403

_LFM2_LAST = {"configs": "lfm2-24b-a2b", "per_layer": _lfm2.NEW[-1]}


def test_lfm2_new_cell_reports_what_it_must(monkeypatch):  # noqa: F811
    doc = copy.deepcopy(_lfm2.MAN.doc)
    for key, last in _LFM2_LAST.items():
        names = [e["name"] for e in doc[key]]
        doc[key] = doc[key][:names.index(last) + 1]
        # what follows LFM2's entries reports none of LFM2's cell
        for later in _lfm2.MAN.doc[key][len(doc[key]):]:
            assert _lfm2.CELL not in later.get("workloads", [])
    monkeypatch.setattr(_lfm2.MAN, "doc", doc)
    _lfm2.test_lfm2_new_cell_reports_what_it_must()
