"""The benchmark's own tests, collected by ``pytest tests/``.

``chipbench/test_*.py`` check that the metrics' readers still match the
names the program emits (kernel ``name=``, ``named_scope``, ``serve/*``
spans); tier-1 is ``pytest tests/``, so they are imported here, not copied.
Each function keeps its own module's globals and helpers; a module-scoped
fixture (``kexaone``, ``lfm2``) comes along by name; no two of the
modules give a test the same name.
"""

from chipbench.test_chipbench import *  # noqa: F401,F403
from chipbench.test_serve_family import *  # noqa: F401,F403
from chipbench.test_serve_lfm2 import *  # noqa: F401,F403
from chipbench.test_trace_stats import *  # noqa: F401,F403
