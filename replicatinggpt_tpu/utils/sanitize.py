"""Runtime sanitizers: the dynamic half of graftlint.

The static rules (analysis/) catch hazards with a syntactic footprint;
these guards catch the ones only visible at run time:

- :class:`CompileGuard` — wraps a jitted callable and fails loudly when
  it compiles more distinct programs than budgeted. Generalizes the
  serve engine's ad-hoc two-program assertion: the engine now guards
  its decode and prefill jits, and the train runner guards the train
  step, so a silent steady-state recompile (shape/dtype drift, a
  weak-type promotion, a committed/uncommitted placement split — the
  exact bug class PR 1 hit) surfaces as an exception naming the
  program instead of as a 40% throughput mystery. A call that added a
  program is a BUILD in the set-up record (``utils.telemetry``).
- :func:`check_in_bounds` — the sanctioned guard for
  ``dynamic_update_slice`` starts (lint rule GL006): asserts on
  concrete values, no-op on tracers (jit callers must bound the index
  host-side — the serving engine does, at admission).
- :func:`donation_report` / :func:`assert_donated` — donation is a
  *request*; XLA can decline it (or the backend may not support it at
  all) and the only symptom is doubled peak HBM. These inspect
  ``jax.Array.is_deleted`` after a donating call to verify the old
  buffers actually died.
- :func:`sanitized` / :func:`sanitize_enabled` — ``GRAFT_SANITIZE=1``
  turns on jax's tracer-leak checker and NaN checks around the train
  and serve loops (opt-in: both checks cost compile time and disable
  some fusions, so they are debug equipment, not defaults).
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Any, Callable, Dict, Iterable, Optional

from .telemetry import setup_record


class RecompileError(RuntimeError):
    """A guarded jit compiled more programs than its budget."""


class DonationError(RuntimeError):
    """Buffers donated to a jitted call are still alive after it."""


class CompileGuard:
    """Budgeted recompile detector around one jitted callable.

    Counts compiled programs via the jit cache size, attributing to
    this guard only the growth observed *across its own calls* —
    module-level jits accumulate programs from every caller (each pool
    shape the serve engine has ever used), so neither the absolute
    size nor cross-call growth means anything to one owner; a compile
    that happened inside a call this guard made is exactly its compile
    count. ``max_programs`` is the number of distinct programs the
    owner expects to trigger (1 for a steady-state step; the first
    compile is legitimate, the second is the bug).

    Raises :class:`RecompileError` from the call that exceeded the
    budget, with the usual suspects listed — by construction the
    offending call is the one that changed something.
    """

    def __init__(self, fn: Callable, name: str, max_programs: int = 1):
        self._fn = fn
        self.name = name
        self.max_programs = max_programs
        self._compiles = 0
        self.calls = 0
        setup_record()       # its listener must hear the first build's stages

    def _cache_size(self) -> int:
        size = getattr(self._fn, "_cache_size", None)
        return int(size()) if callable(size) else 0

    @property
    def compiles(self) -> int:
        """Programs compiled during this guard's own calls."""
        return self._compiles

    def expect(self, n: int) -> "CompileGuard":
        """Widen the budget (e.g. a caller that legitimately runs two
        shapes through one jit)."""
        self.max_programs = n
        return self

    def check(self) -> int:
        n = self.compiles
        if n > self.max_programs:
            raise RecompileError(
                f"CompileGuard[{self.name}]: {n} programs compiled "
                f"(budget {self.max_programs}) over {self.calls} call(s). "
                f"A steady-state jit recompiled — usual causes: an input "
                f"changed shape/dtype, a Python scalar flipped weak-type, "
                f"an input's committed/uncommitted placement changed "
                f"(device_put'd array vs raw numpy), or a static arg got "
                f"a new value. Run with GRAFT_SANITIZE=1 and see "
                f"docs/graftlint_rules.md for the static-side rules.")
        return n

    def __call__(self, *args, **kwargs):
        before = self._cache_size()
        t0 = time.perf_counter()
        out = self._fn(*args, **kwargs)
        self.calls += 1
        # growth across THIS call only: programs other owners of the
        # same (module-level) jit compile between our calls are theirs
        grew = self._cache_size() - before
        if grew > 0:
            # a build: the program's first call, kept in the set-up record
            self._compiles += grew
            setup_record().record_build(self.name, t0, time.perf_counter())
        self.check()
        return out

    def stats(self) -> Dict[str, int]:
        return {"calls": self.calls, "compiles": self.compiles,
                "budget": self.max_programs}


# ---------------------------------------------------------------------------
# in-bounds guard (the GL006 sanctioned pattern)
# ---------------------------------------------------------------------------

def _concrete_int(x: Any) -> Optional[int]:
    """Python int of ``x`` when it is host-knowable; None for tracers
    (and anything else that refuses int())."""
    try:
        return int(x)
    except Exception:
        return None


def check_in_bounds(start: Any, length: Any, size: Any,
                    what: str = "dynamic_update_slice") -> bool:
    """Enforce ``0 <= start`` and ``start + length <= size`` when the
    values are concrete; return False (unchecked) under tracing.

    This is the sanctioned guard for ``jax.lax.dynamic_update_slice``
    (lint rule GL006): out-of-bounds starts do not raise, they CLAMP —
    which under a cache write means silently overwriting valid earlier
    entries (PR 1's chunked-prefill corruption). Inside a jit the
    start is a tracer and cannot be checked here; the host-side caller
    owns the bound then (e.g. the serve engine's admission check), and
    eager/debug runs get a hard IndexError (a real exception, not an
    ``assert`` — the guard must survive ``python -O``). ``start`` may
    be a vector (per-slot positions): its min/max are checked.
    """
    sz = _concrete_int(size)
    ln = _concrete_int(length)
    if sz is None or ln is None:
        return False
    lo = hi = None
    try:                      # vector starts: bound the extremes
        import numpy as np
        arr = np.asarray(start)
        if arr.dtype != object and arr.size:
            lo, hi = int(arr.min()), int(arr.max())
    except Exception:
        lo = hi = _concrete_int(start)
    if lo is None or hi is None:
        return False
    if lo < 0 or hi + ln > sz:
        # a real exception, not `assert`: these guards protect against
        # silent cache corruption and must survive `python -O`
        raise IndexError(
            f"{what}: start {lo}..{hi} + length {ln} exceeds size {sz} — "
            f"dynamic_update_slice would CLAMP and corrupt earlier entries")
    return True


# ---------------------------------------------------------------------------
# donation verification
# ---------------------------------------------------------------------------

def donation_supported() -> bool:
    """Whether the default backend honors buffer donation at all (CPU
    ignores it; asserting there would always fail)."""
    import jax
    return jax.default_backend() in ("tpu", "gpu")


def donation_report(tree: Any) -> Dict[str, int]:
    """How many array leaves of ``tree`` have been invalidated.

    Call on the *inputs you donated* after the jitted call: leaves
    still alive mean XLA declined the donation (layout mismatch, or the
    buffer is aliased elsewhere) and peak memory is double what the
    donate_argnums annotation promises."""
    import jax
    deleted = live = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        is_deleted = getattr(leaf, "is_deleted", None)
        if not callable(is_deleted):
            continue
        if is_deleted():
            deleted += 1
        else:
            live += 1
    return {"deleted": deleted, "live": live}


def assert_donated(tree: Any, what: str = "donated input") -> bool:
    """Raise :class:`DonationError` if donated buffers survived the
    call — only on backends that support donation (returns False,
    checked nothing, elsewhere)."""
    if not donation_supported():
        return False
    rep = donation_report(tree)
    if rep["live"]:
        raise DonationError(
            f"{what}: {rep['live']} of {rep['live'] + rep['deleted']} "
            f"donated buffers still alive after the call — XLA declined "
            f"the donation (layout/aliasing mismatch); peak HBM is "
            f"double what donate_argnums promises")
    return True


# ---------------------------------------------------------------------------
# GRAFT_SANITIZE mode
# ---------------------------------------------------------------------------

def sanitize_enabled() -> bool:
    """Opt-in via ``GRAFT_SANITIZE=1`` (any value but ''/'0')."""
    return os.environ.get("GRAFT_SANITIZE", "") not in ("", "0")


@contextlib.contextmanager
def sanitized(enable: Optional[bool] = None):
    """Enable jax tracer-leak checking + NaN checks inside the block
    (both restored on exit). ``enable=None`` follows GRAFT_SANITIZE;
    the train runner and serve engine wrap their loops in this, so
    ``GRAFT_SANITIZE=1 python -m replicatinggpt_tpu train ...`` is a
    full sanitizer run with no code changes."""
    if enable is None:
        enable = sanitize_enabled()
    if not enable:
        yield False
        return
    import jax
    prev_leaks = jax.config.jax_check_tracer_leaks
    prev_nans = jax.config.jax_debug_nans
    jax.config.update("jax_check_tracer_leaks", True)
    jax.config.update("jax_debug_nans", True)
    try:
        yield True
    finally:
        jax.config.update("jax_check_tracer_leaks", prev_leaks)
        jax.config.update("jax_debug_nans", prev_nans)


def check_finite(value: Any, what: str = "value") -> None:
    """Host-side finiteness check for already-fetched scalars (the
    sanitize-mode hook on the train loop's logged loss)."""
    import math
    v = float(value)
    if not math.isfinite(v):
        raise FloatingPointError(f"{what} is {v} — non-finite under "
                                 f"GRAFT_SANITIZE")
