"""graftlint rule registry: JAX hazards as pure-AST passes.

Every expensive JAX failure mode this package has hit by hand — silent
recompiles from tracer-dependent Python control flow, retained donated
buffers, RNG key reuse, per-step host round-trips, the
``dynamic_update_slice`` clamp corruption PR 1 debugged in the serving
prefill — leaves a recognizable syntactic footprint. These rules match
those footprints with ``ast`` only: no jax import, no tracing, no
device, so ``python -m replicatinggpt_tpu lint`` is a sub-second
CPU-only tier-1 check.

Each rule is registered with an ID, a rationale, and a bad/good example
pair; ``docgen.render_rule_docs`` turns the registry into
``docs/graftlint_rules.md`` and ``tests/test_lint.py`` parametrizes
over it, so a rule cannot exist without docs and fixture coverage.

Suppression: ``# graftlint: disable=GL004`` on the flagged line, or
``# graftlint: disable-file=GL004`` anywhere in the file (see
linter.py); pre-existing findings live in the committed baseline
(baseline.py) so the lint gate only fails on NEW hazards.

Static analysis over a dynamic language is heuristic by construction:
the rules are tuned to the idioms of this codebase (decorator-jitted
functions, ``partial(jax.jit, ...)``, module-level jits) and prefer
missing an exotic spelling over drowning real findings in noise.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# data model
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Finding:
    """One lint hit. ``text`` is the stripped source line — the baseline
    matches on (path, rule, text) rather than line numbers, so findings
    survive unrelated edits that shift lines. ``severity`` is assigned
    by the driver from the per-directory tier map (tests/ findings are
    warnings); only errors gate CI or enter the baseline."""

    path: str
    rule: str
    line: int
    col: int
    message: str
    text: str
    severity: str = "error"

    def format(self) -> str:
        tag = "" if self.severity == "error" else f" {self.severity}:"
        return (f"{self.path}:{self.line}:{self.col}:{tag} "
                f"{self.rule} {self.message}")


@dataclass(frozen=True)
class Rule:
    """``checker`` is the per-file syntactic pass; ``project_checker``
    (v2) runs once per lint invocation over the whole-project
    :class:`~.callgraph.ProjectIndex` and is how a rule sees across
    function and file boundaries. A rule may have either or both — the
    driver runs both and merges the findings under one rule id."""

    id: str
    name: str
    rationale: str
    bad: str
    good: str
    checker: Optional[Callable[[ast.Module, Sequence[str], str],
                               List[Finding]]] = None
    project_checker: Optional[Callable[..., List[Finding]]] = None


RULES: Dict[str, Rule] = {}


def _register(rule: Rule) -> Rule:
    assert rule.id not in RULES, f"duplicate rule id {rule.id}"
    assert rule.checker or rule.project_checker, rule.id
    RULES[rule.id] = rule
    return rule


def _project(check_name: str):
    """Lazy dispatch into dataflow.py / contracts.py (rules.py is
    imported by both, so the project checkers bind at call time, not
    import time). dataflow owns the callgraph-walking families;
    contracts owns the wire/config/metrics contract registry (v3)."""
    def run(index):
        from . import contracts, dataflow
        target = getattr(dataflow, check_name, None)
        if target is None:
            target = getattr(contracts, check_name)
        return target(index)
    run.__name__ = check_name
    return run


# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------

def dotted(node: ast.AST) -> Optional[str]:
    """'jax.lax.dynamic_update_slice' for a Name/Attribute chain, else
    None (calls, subscripts etc. in the chain give up)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


_JIT_WRAPPERS = {"jax.jit", "jit", "pjit", "jax.pmap", "pmap",
                 "jax.experimental.pjit.pjit"}
_PARTIAL = {"functools.partial", "partial"}


def _line_of(node: ast.AST, lines: Sequence[str]) -> str:
    i = getattr(node, "lineno", 1) - 1
    return lines[i].strip() if 0 <= i < len(lines) else ""


def _finding(rule_id: str, node: ast.AST, message: str, path: str,
             lines: Sequence[str]) -> Finding:
    return Finding(path=path, rule=rule_id, line=node.lineno,
                   col=node.col_offset, message=message,
                   text=_line_of(node, lines))


def _jit_wrap_call(node: ast.AST) -> Optional[ast.Call]:
    """The jax.jit(...) Call under ``node`` when node is a jit wrapper
    expression: ``jax.jit``, ``jax.jit(...)``, or
    ``partial(jax.jit, ...)``. None otherwise."""
    if isinstance(node, ast.Call):
        f = dotted(node.func)
        if f in _JIT_WRAPPERS:
            return node
        if f in _PARTIAL and node.args and dotted(node.args[0]) in _JIT_WRAPPERS:
            return node
    return None


def _is_jit_wrapper(node: ast.AST) -> bool:
    return (dotted(node) in _JIT_WRAPPERS) or _jit_wrap_call(node) is not None


def _jit_kwargs(node: ast.AST) -> Dict[str, ast.expr]:
    call = _jit_wrap_call(node)
    if call is None:
        return {}
    return {kw.arg: kw.value for kw in call.keywords if kw.arg}


def _const_str_items(node: Optional[ast.expr]) -> List[str]:
    """String elements of a tuple/list/str constant expression."""
    if node is None:
        return []
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)]
    return []


def _const_int_items(node: Optional[ast.expr]) -> List[int]:
    if node is None:
        return []
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, int)]
    return []


def _param_names(fn: ast.FunctionDef) -> List[str]:
    a = fn.args
    return [p.arg for p in (*a.posonlyargs, *a.args, *a.kwonlyargs)]


def _static_param_names(fn: ast.FunctionDef,
                        kwargs: Dict[str, ast.expr]) -> set:
    static = set(_const_str_items(kwargs.get("static_argnames")))
    params = _param_names(fn)
    for i in _const_int_items(kwargs.get("static_argnums")):
        if 0 <= i < len(params):
            static.add(params[i])
    return static


def _jit_decorator(fn: ast.FunctionDef) -> Optional[ast.AST]:
    for dec in fn.decorator_list:
        if _is_jit_wrapper(dec):
            return dec
    return None


def _top_level_functions(tree: ast.Module) -> List[ast.FunctionDef]:
    """Module-level and method-level defs (nested defs analyzed as part
    of their parent, not separately — guards in the outer scope bless
    the whole lexical function)."""
    out: List[ast.FunctionDef] = []
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.append(node)
        elif isinstance(node, ast.ClassDef):
            out.extend(n for n in node.body
                       if isinstance(n, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)))
    return out


#: keyed on id(tree): every per-file rule asks for the same function
#: list, and re-walking a large module once per rule dominates the
#: per-file pass. The strong tree reference makes id() aliasing
#: impossible while an entry lives; the linter clears the cache at the
#: start of each run so trees don't accumulate across runs.
_ALL_FUNCTIONS_CACHE: Dict[int, Tuple[ast.Module, List[ast.FunctionDef]]] = {}


def _all_functions(tree: ast.Module) -> List[ast.FunctionDef]:
    hit = _ALL_FUNCTIONS_CACHE.get(id(tree))
    if hit is not None and hit[0] is tree:
        return hit[1]
    fns = [n for n in ast.walk(tree)
           if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    _ALL_FUNCTIONS_CACHE[id(tree)] = (tree, fns)
    return fns


# ---------------------------------------------------------------------------
# GL001 — tracer-dependent Python control flow in jitted functions
# ---------------------------------------------------------------------------

def _check_tracer_branch(tree, lines, path):
    findings = []
    for fn in _all_functions(tree):
        dec = _jit_decorator(fn)
        if dec is None:
            continue
        static = _static_param_names(fn, _jit_kwargs(dec))
        traced = {n for n in _param_names(fn) if n not in static} - {"self"}
        for node in ast.walk(fn):
            if not isinstance(node, (ast.If, ast.While)):
                continue
            test = node.test
            # `x is None` / `x is not None` on a traced name is a static
            # Python identity check, not a tracer branch
            if (isinstance(test, ast.Compare)
                    and all(isinstance(op, (ast.Is, ast.IsNot))
                            for op in test.ops)):
                continue
            used = {n.id for n in ast.walk(test) if isinstance(n, ast.Name)}
            hit = sorted(used & traced)
            if hit:
                kw = "while" if isinstance(node, ast.While) else "if"
                findings.append(_finding(
                    "GL001", node,
                    f"Python `{kw}` on traced argument(s) {', '.join(hit)} "
                    f"inside jitted `{fn.name}` — branches on tracers raise "
                    f"ConcretizationTypeError or silently retrace per value; "
                    f"use jnp.where/lax.cond or mark the arg static",
                    path, lines))
    return findings


_register(Rule(
    id="GL001", name="tracer-branch",
    rationale=(
        "Python `if`/`while` on a traced value inside a jitted function "
        "either crashes (ConcretizationTypeError) or — when the value is "
        "accidentally concrete, e.g. a host scalar passed per step — "
        "recompiles the program for every distinct value. Recompiles are "
        "the top TPU-time sink in the pjit scaling postmortems this repo "
        "is built on."),
    bad="""\
@jax.jit
def step(x, n):
    if n > 0:            # n is traced: retrace/crash
        x = x * n
    return x
""",
    good="""\
@partial(jax.jit, static_argnames=("n",))
def step(x, n):
    if n > 0:            # n is a static (hashable) Python value
        x = x * n
    return x
# ...or keep n traced and branch on device: jnp.where(n > 0, x * n, x)
""",
    checker=_check_tracer_branch))


# ---------------------------------------------------------------------------
# GL002 — device computation at module import time
# ---------------------------------------------------------------------------

_GL002_PREFIXES = ("jnp.", "jax.numpy.", "jax.random.")
_GL002_EXACT = {"jax.device_put"}


def _gl002_call_hit(call: ast.Call) -> bool:
    f = dotted(call.func)
    if f is None:
        return False
    return f in _GL002_EXACT or any(f.startswith(p) for p in _GL002_PREFIXES)


def _check_module_scope_jnp(tree, lines, path):
    findings = []

    def scan(node):
        """Walk expressions evaluated at import time, skipping function
        and lambda BODIES (their defaults/decorators DO evaluate at
        import and are scanned)."""
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for d in node.decorator_list:
                scan(d)
            for default in (*node.args.defaults, *node.args.kw_defaults):
                if default is not None:
                    scan(default)
            return
        if isinstance(node, ast.Lambda):
            return
        if isinstance(node, ast.Call) and _gl002_call_hit(node):
            findings.append(_finding(
                "GL002", node,
                f"`{dotted(node.func)}(...)` runs at module import: it "
                f"allocates device memory / compiles before any jit, on "
                f"whatever backend import-time default is, and once per "
                f"process — build arrays inside the jitted fn or lazily",
                path, lines))
        for child in ast.iter_child_nodes(node):
            scan(child)

    for stmt in tree.body:
        scan(stmt)
    return findings


_register(Rule(
    id="GL002", name="module-scope-device-call",
    rationale=(
        "A `jnp.*` / `jax.random.*` call at module scope executes during "
        "import: it initializes the backend early (breaking later "
        "platform/flag configuration), allocates device memory that "
        "lives for the process, and runs eagerly un-jitted. Constants "
        "built this way also become committed arrays whose placement "
        "can split jit cache keys."),
    bad="""\
import jax.numpy as jnp
MASK = jnp.tril(jnp.ones((1024, 1024)))   # device alloc at import
""",
    good="""\
import numpy as np
MASK = np.tril(np.ones((1024, 1024)))     # host constant; or build
                                          # inside the jitted function
""",
    checker=_check_module_scope_jnp,
    project_checker=_project("check_device_call_at_import")))


# ---------------------------------------------------------------------------
# GL003 — PRNG key reuse (>= 2 consumers without split)
# ---------------------------------------------------------------------------

_KEY_SOURCES = {"jax.random.PRNGKey", "jax.random.key", "jax.random.split",
                "jax.random.fold_in", "random.PRNGKey", "random.split",
                "random.fold_in"}
_KEY_DERIVERS = {"jax.random.split", "jax.random.fold_in", "random.split",
                 "random.fold_in", "jax.random.clone"}


def _is_key_source(node: ast.expr) -> bool:
    if isinstance(node, ast.Call) and dotted(node.func) in _KEY_SOURCES:
        return True
    if isinstance(node, ast.Subscript):   # keys[0] of a split
        return _is_key_source(node.value)
    return False


class _KeyReuseScanner:
    """Linear, source-order walk of one function body. Tracks names
    bound to PRNG keys; any call consuming a key name (except
    split/fold_in derivation) counts one use — two uses without an
    intervening rebind is reuse. A consumption inside a loop deeper
    than the key's binding counts twice (the classic per-iteration
    reuse)."""

    def __init__(self, fn, lines, path):
        self.fn, self.lines, self.path = fn, lines, path
        self.keys: Dict[str, dict] = {}      # name -> {depth, uses}
        self.findings: List[Finding] = []
        self.depth = 0

    def run(self):
        for stmt in self.fn.body:
            self._stmt(stmt)
        return self.findings

    def _bind(self, name: str, value: Optional[ast.expr]):
        if value is not None and _is_key_source(value):
            self.keys[name] = {"depth": self.depth, "uses": 0,
                               "flagged": False}
        else:
            self.keys.pop(name, None)

    def _targets(self, target: ast.expr, value: Optional[ast.expr]):
        if isinstance(target, ast.Name):
            self._bind(target.id, value)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                if isinstance(e, ast.Name):
                    # tuple-unpack of a split: every element is a key
                    self._bind(e.id, value)

    def _consume(self, call: ast.Call):
        f = dotted(call.func)
        derive = f in _KEY_DERIVERS
        for arg in (*call.args, *(kw.value for kw in call.keywords)):
            if isinstance(arg, ast.Name) and arg.id in self.keys:
                rec = self.keys[arg.id]
                if derive:
                    continue
                rec["uses"] += 2 if self.depth > rec["depth"] else 1
                if rec["uses"] >= 2 and not rec["flagged"]:
                    rec["flagged"] = True
                    self.findings.append(_finding(
                        "GL003", call,
                        f"PRNG key `{arg.id}` consumed more than once "
                        f"without jax.random.split — every consumer sees "
                        f"the SAME randomness (correlated samples); split "
                        f"or fold_in a fresh key per consumer",
                        self.path, self.lines))

    def _expr(self, node: ast.AST):
        for call in ast.walk(node):
            if isinstance(call, ast.Call):
                self._consume(call)

    def _stmt(self, stmt: ast.stmt):
        if isinstance(stmt, ast.Assign):
            self._expr(stmt.value)
            for t in stmt.targets:
                self._targets(t, stmt.value)
        elif isinstance(stmt, ast.AnnAssign):
            if stmt.value is not None:
                self._expr(stmt.value)
            if isinstance(stmt.target, ast.Name):
                self._bind(stmt.target.id, stmt.value)
        elif isinstance(stmt, ast.AugAssign):
            self._expr(stmt.value)
            if isinstance(stmt.target, ast.Name):
                self.keys.pop(stmt.target.id, None)
        elif isinstance(stmt, (ast.For, ast.AsyncFor)):
            self._expr(stmt.iter)
            self.depth += 1
            self._targets(stmt.target, stmt.iter)
            for s in (*stmt.body, *stmt.orelse):
                self._stmt(s)
            self.depth -= 1
        elif isinstance(stmt, ast.While):
            self._expr(stmt.test)
            self.depth += 1
            for s in (*stmt.body, *stmt.orelse):
                self._stmt(s)
            self.depth -= 1
        elif isinstance(stmt, ast.If):
            self._expr(stmt.test)
            # branches are mutually exclusive: walk each from the same
            # pre-branch state and keep the worst-case use count per key
            # (a consumer in `if` plus one in `else` is NOT reuse)
            snap = {n: dict(rec) for n, rec in self.keys.items()}
            for s in stmt.body:
                self._stmt(s)
            after_body = self.keys
            self.keys = snap
            for s in stmt.orelse:
                self._stmt(s)
            # a body that cannot fall through (return/raise) contributes
            # nothing to the statements after the If — the fall-through
            # path IS the implicit else
            terminal = (ast.Return, ast.Raise, ast.Continue, ast.Break)
            if stmt.body and isinstance(stmt.body[-1], terminal):
                return
            for n, rec in after_body.items():
                if n in self.keys:
                    cur = self.keys[n]
                    cur["uses"] = max(cur["uses"], rec["uses"])
                    cur["flagged"] = cur["flagged"] or rec["flagged"]
                else:
                    self.keys[n] = rec
        elif isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                self._expr(item.context_expr)
            for s in stmt.body:
                self._stmt(s)
        elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            pass                    # nested defs get their own scan
        elif isinstance(stmt, (ast.Return, ast.Expr)):
            if stmt.value is not None:
                self._expr(stmt.value)
        elif isinstance(stmt, ast.Try):
            for s in (*stmt.body, *stmt.orelse, *stmt.finalbody):
                self._stmt(s)
            for h in stmt.handlers:
                for s in h.body:
                    self._stmt(s)
        else:
            self._expr(stmt)


def _check_key_reuse(tree, lines, path):
    findings = []
    for fn in _all_functions(tree):
        findings.extend(_KeyReuseScanner(fn, lines, path).run())
    return findings


_register(Rule(
    id="GL003", name="rng-key-reuse",
    rationale=(
        "jax.random is splittable, not stateful: passing one key to two "
        "consumers gives both the SAME stream. Correlated dropout masks "
        "or init tensors are silent statistical corruption — the run "
        "trains, the loss curve just quietly lies. A consumer inside a "
        "loop over the key's binding reuses it every iteration."),
    bad="""\
key = jax.random.PRNGKey(0)
a = jax.random.normal(key, (8,))
b = jax.random.normal(key, (8,))      # identical to `a`
""",
    good="""\
key = jax.random.PRNGKey(0)
ka, kb = jax.random.split(key)
a = jax.random.normal(ka, (8,))
b = jax.random.normal(kb, (8,))
""",
    checker=_check_key_reuse))


# ---------------------------------------------------------------------------
# GL004 — host-device sync inside step loops
# ---------------------------------------------------------------------------

_GL004_FUNCS = {"np.asarray": "np.asarray", "numpy.asarray": "np.asarray",
                "np.array": "np.array", "numpy.array": "np.array",
                "jax.device_get": "jax.device_get"}


def _check_host_sync_in_loop(tree, lines, path):
    findings = []

    def scan(node, loop_depth):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            body = node.body if not isinstance(node, ast.Lambda) else []
            for child in body:
                scan(child, 0)       # fresh function: loop depth resets
            return
        if isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
            loop_depth += 1
        if loop_depth > 0 and isinstance(node, ast.Call):
            what = None
            f = dotted(node.func)
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item" and not node.args):
                what = ".item()"
            elif f in _GL004_FUNCS:
                what = _GL004_FUNCS[f]
            elif (isinstance(node.func, ast.Name)
                  and node.func.id == "float" and len(node.args) == 1
                  and not isinstance(node.args[0], ast.Constant)):
                what = "float(...)"
            if what:
                findings.append(_finding(
                    "GL004", node,
                    f"`{what}` inside a loop forces a device->host sync "
                    f"every iteration (stalls the dispatch pipeline); "
                    f"accumulate on device and fetch once after the loop",
                    path, lines))
        for child in ast.iter_child_nodes(node):
            scan(child, loop_depth)

    for stmt in tree.body:
        scan(stmt, 0)
    return findings


_register(Rule(
    id="GL004", name="host-sync-in-loop",
    rationale=(
        "`float()` / `.item()` / `np.asarray()` on a device value blocks "
        "until the device finishes — inside a step loop that's one full "
        "pipeline stall per iteration (the TPUv4 pjit postmortem "
        "attributes most lost time to exactly these host stalls, not "
        "FLOPs). This package's eval loop paid one round-trip per eval "
        "batch until the PR that introduced this linter fixed it."),
    bad="""\
total = 0.0
for _ in range(k):
    total += float(eval_step(params, batch))   # sync per batch
""",
    good="""\
total = None
for _ in range(k):
    loss = eval_step(params, batch)            # stays on device
    total = loss if total is None else total + loss
mean = float(total) / k                        # ONE sync per split
""",
    checker=_check_host_sync_in_loop,
    project_checker=_project("check_sync_through_helpers")))


# ---------------------------------------------------------------------------
# GL005 — jit over state/cache pytrees without donation
# ---------------------------------------------------------------------------

_DONATABLE = {"state", "opt_state", "cache", "kv_cache", "caches",
              "train_state", "carry"}


def _check_missing_donation(tree, lines, path):
    findings = []
    module_fns = {fn.name: fn for fn in _all_functions(tree)}

    def check(fn: ast.FunctionDef, site: ast.AST, kwargs):
        if "donate_argnums" in kwargs or "donate_argnames" in kwargs:
            return
        hit = sorted(set(_param_names(fn)) & _DONATABLE)
        if hit:
            findings.append(_finding(
                "GL005", site,
                f"jit of `{fn.name}` takes {', '.join(hit)} but donates "
                f"nothing — without donate_argnums/donate_argnames the "
                f"old buffers stay live across the call, doubling HBM "
                f"for update-in-place state (OOM at exactly the model "
                f"size that otherwise fits)",
                path, lines))

    for fn in _all_functions(tree):
        dec = _jit_decorator(fn)
        if dec is not None:
            check(fn, dec, _jit_kwargs(dec))
    for node in ast.walk(tree):
        call = _jit_wrap_call(node)
        if call is None or not call.args:
            continue
        # jax.jit(f, ...) / partial(jax.jit, f, ...) with f a plain
        # function defined in this module
        first = call.args[0]
        if dotted(first) in _JIT_WRAPPERS:        # the partial spelling
            if len(call.args) < 2:
                continue
            first = call.args[1]
        if isinstance(first, ast.Name) and first.id in module_fns:
            check(module_fns[first.id], call, _jit_kwargs(node))
    return findings


_register(Rule(
    id="GL005", name="missing-donation",
    rationale=(
        "A jitted update step that takes a large pytree (train state, KV "
        "cache) and returns its successor keeps BOTH alive unless the "
        "input is donated — the peak-HBM doubling that decides whether "
        "a model fits. Donation also lets XLA alias the update in "
        "place. Heuristic: parameters named state/cache/opt_state/... "
        "are update-in-place pytrees."),
    bad="""\
@jax.jit
def update(state, batch):        # old state buffers stay live
    return state.apply(batch)
""",
    good="""\
@partial(jax.jit, donate_argnames=("state",))
def update(state, batch):        # old buffers reused for the new state
    return state.apply(batch)
""",
    checker=_check_missing_donation,
    project_checker=_project("check_use_after_donate")))


# ---------------------------------------------------------------------------
# GL006 — dynamic_update_slice without an in-bounds guard
# ---------------------------------------------------------------------------

_DUS = {"jax.lax.dynamic_update_slice", "lax.dynamic_update_slice",
        "jax.lax.dynamic_update_slice_in_dim",
        "lax.dynamic_update_slice_in_dim"}
_BOUNDS_GUARDS = ("check_in_bounds", "assert_in_bounds", "checkify.check")


def _const_like(node: ast.expr, const_names: set) -> bool:
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.Name):
        return node.id in const_names
    if isinstance(node, ast.Call):
        f = dotted(node.func)
        if f in ("jnp.int32", "jnp.uint32", "int") and node.args:
            return _const_like(node.args[0], const_names)
    if isinstance(node, ast.UnaryOp):
        return _const_like(node.operand, const_names)
    return False


def _check_unguarded_dus(tree, lines, path):
    findings = []
    for fn in _top_level_functions(tree):
        # one-level local constant/tuple resolution
        assigns: Dict[str, ast.expr] = {}
        for node in ast.walk(fn):
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                assigns[node.targets[0].id] = node.value
        const_names = {n for n, v in assigns.items()
                       if _const_like(v, set())}
        # clamped names: bound from jnp.minimum / jnp.clip / `%`
        clamped = {n for n, v in assigns.items()
                   if (isinstance(v, ast.Call)
                       and dotted(v.func) in ("jnp.minimum", "jnp.clip",
                                              "jax.numpy.minimum",
                                              "jax.numpy.clip"))
                   or (isinstance(v, ast.BinOp)
                       and isinstance(v.op, ast.Mod))}
        # blessing: a sanctioned guard call anywhere in the function, or
        # an `assert` naming one of the start indices
        guard_called = any(
            isinstance(n, ast.Call)
            and dotted(n.func) is not None
            and (dotted(n.func) in _BOUNDS_GUARDS
                 or dotted(n.func).split(".")[-1] in _BOUNDS_GUARDS)
            for n in ast.walk(fn))
        assert_names: set = set()
        for n in ast.walk(fn):
            if isinstance(n, ast.Assert):
                assert_names |= {x.id for x in ast.walk(n.test)
                                 if isinstance(x, ast.Name)}

        for call in ast.walk(fn):
            if not (isinstance(call, ast.Call)
                    and dotted(call.func) in _DUS):
                continue
            if guard_called:
                continue
            start_args = call.args[2:]
            names: set = set()
            for a in start_args:
                if isinstance(a, ast.Name) and a.id in assigns:
                    a = assigns[a.id]
                for x in ast.walk(a):
                    if isinstance(x, ast.Name):
                        names.add(x.id)
            nonconst = {n for n in names if n not in const_names}
            if not nonconst:
                continue
            if nonconst & clamped or nonconst & assert_names:
                continue
            findings.append(_finding(
                "GL006", call,
                f"dynamic_update_slice start index ({', '.join(sorted(nonconst))}) "
                f"has no in-bounds guard in `{fn.name}` — out-of-bounds "
                f"starts silently CLAMP and overwrite valid earlier data "
                f"(the serving prefill corruption bug); add "
                f"check_in_bounds(...) (utils.sanitize) or an assert on "
                f"the index",
                path, lines))
    return findings


_register(Rule(
    id="GL006", name="unguarded-dynamic-update-slice",
    rationale=(
        "`jax.lax.dynamic_update_slice` does not raise on out-of-bounds "
        "start indices: it CLAMPS them, silently overwriting valid "
        "earlier data. PR 1's chunked-prefill bug corrupted KV-cache "
        "entries exactly this way. The sanctioned pattern is a "
        "`check_in_bounds(start, length, size)` call "
        "(utils.sanitize) — or an `assert` naming the index — in the "
        "same function."),
    bad="""\
def write(buf, row, pos):
    return jax.lax.dynamic_update_slice(buf, row, (pos, 0))
""",
    good="""\
from replicatinggpt_tpu.utils.sanitize import check_in_bounds

def write(buf, row, pos):
    check_in_bounds(pos, row.shape[0], buf.shape[0])  # asserts when
    return jax.lax.dynamic_update_slice(buf, row, (pos, 0))  # concrete
""",
    checker=_check_unguarded_dus))


# ---------------------------------------------------------------------------
# GL007 — non-hashable values for static jit parameters
# ---------------------------------------------------------------------------

_MUTABLE_LITERALS = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp,
                     ast.SetComp)


def _check_unhashable_static(tree, lines, path):
    findings = []
    # jitted defs and their static param names
    static_of: Dict[str, set] = {}
    for fn in _all_functions(tree):
        dec = _jit_decorator(fn)
        if dec is None:
            continue
        static = _static_param_names(fn, _jit_kwargs(dec))
        if static:
            static_of[fn.name] = static
        # (a) static param whose DEFAULT is a mutable literal
        a = fn.args
        params = [p.arg for p in (*a.posonlyargs, *a.args)]
        for p, d in zip(params[len(params) - len(a.defaults):], a.defaults):
            if p in static and isinstance(d, _MUTABLE_LITERALS):
                findings.append(_finding(
                    "GL007", d,
                    f"static arg `{p}` of jitted `{fn.name}` defaults to a "
                    f"non-hashable {type(d).__name__.lower()} — jit "
                    f"statics are dict keys; this raises "
                    f"`unhashable type` at the first call (use a tuple / "
                    f"frozen dataclass)",
                    path, lines))
        for p, d in zip([p.arg for p in a.kwonlyargs], a.kw_defaults):
            if d is not None and p in static and isinstance(d, _MUTABLE_LITERALS):
                findings.append(_finding(
                    "GL007", d,
                    f"static arg `{p}` of jitted `{fn.name}` defaults to a "
                    f"non-hashable {type(d).__name__.lower()}",
                    path, lines))
    # assigned wrappers: g = jax.jit(f, static_argnames=(...))
    for node in ast.walk(tree):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)):
            call = _jit_wrap_call(node.value)
            if call is not None:
                statics = set(_const_str_items(
                    _jit_kwargs(node.value).get("static_argnames")))
                if statics:
                    static_of[node.targets[0].id] = statics
    # (b) callsites passing a mutable literal to a known static kwarg
    for call in ast.walk(tree):
        if not isinstance(call, ast.Call):
            continue
        name = dotted(call.func)
        statics = static_of.get(name or "", set())
        if not statics:
            continue
        for kw in call.keywords:
            if kw.arg in statics and isinstance(kw.value, _MUTABLE_LITERALS):
                findings.append(_finding(
                    "GL007", kw.value,
                    f"call passes a non-hashable "
                    f"{type(kw.value).__name__.lower()} as static arg "
                    f"`{kw.arg}` of jitted `{name}` — raises `unhashable "
                    f"type: ...` (pass a tuple / frozen value)",
                    path, lines))
    return findings


_register(Rule(
    id="GL007", name="unhashable-static-arg",
    rationale=(
        "jit's static arguments become cache-dictionary keys: a list / "
        "dict / set value raises `TypeError: unhashable type` at call "
        "time — and a mutable-but-hashable value is worse, silently "
        "splitting the cache per identity. Statics should be tuples, "
        "strings, numbers, or frozen dataclasses (like this package's "
        "ModelConfig)."),
    bad="""\
@partial(jax.jit, static_argnames=("dims",))
def pool(x, dims=[1, 2]):        # unhashable at first call
    return x.sum(tuple(dims))
""",
    good="""\
@partial(jax.jit, static_argnames=("dims",))
def pool(x, dims=(1, 2)):        # hashable static
    return x.sum(dims)
""",
    checker=_check_unhashable_static))


# ---------------------------------------------------------------------------
# GL008 — pmap/shard_map bodies capturing module globals
# ---------------------------------------------------------------------------

_SPMD_WRAPPERS = {"jax.pmap", "pmap", "shard_map",
                  "jax.experimental.shard_map.shard_map"}


def _spmd_decorator(fn: ast.FunctionDef) -> bool:
    for dec in fn.decorator_list:
        if dotted(dec) in _SPMD_WRAPPERS:
            return True
        if isinstance(dec, ast.Call):
            f = dotted(dec.func)
            if f in _SPMD_WRAPPERS:
                return True
            if f in _PARTIAL and dec.args and dotted(dec.args[0]) in _SPMD_WRAPPERS:
                return True
    return False


def _check_spmd_global_capture(tree, lines, path):
    # module-scope mutable-looking globals: lowercase simple assignments
    globals_: set = set()
    for stmt in tree.body:
        if isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                if (isinstance(t, ast.Name) and not t.id.startswith("__")
                        and not t.id.isupper()
                        and not isinstance(stmt.value,
                                           (ast.Lambda, ast.Constant))):
                    globals_.add(t.id)
    if not globals_:
        return []
    # functions handed to pmap/shard_map by name
    spmd_fns = {fn.name for fn in _all_functions(tree) if _spmd_decorator(fn)}
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call) and dotted(node.func) in _SPMD_WRAPPERS
                and node.args and isinstance(node.args[0], ast.Name)):
            spmd_fns.add(node.args[0].id)
    findings = []
    for fn in _all_functions(tree):
        if fn.name not in spmd_fns:
            continue
        local = set(_param_names(fn))
        for n in ast.walk(fn):
            if isinstance(n, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                tgts = n.targets if isinstance(n, ast.Assign) else [n.target]
                for t in tgts:
                    for x in ast.walk(t):
                        if isinstance(x, ast.Name):
                            local.add(x.id)
        seen = set()
        for n in ast.walk(fn):
            if (isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)
                    and n.id in globals_ and n.id not in local
                    and n.id not in seen):
                seen.add(n.id)
                findings.append(_finding(
                    "GL008", n,
                    f"`{fn.name}` runs under pmap/shard_map but captures "
                    f"module global `{n.id}` — captured arrays are "
                    f"broadcast into every program (replicated HBM copy, "
                    f"silent retrace when rebound); pass it as an "
                    f"argument with an explicit spec",
                    path, lines))
    return findings


_register(Rule(
    id="GL008", name="spmd-global-capture",
    rationale=(
        "A function run under pmap/shard_map that closes over a module "
        "global embeds that value into the compiled program: arrays get "
        "broadcast to every device (a full replicated copy in HBM, "
        "outside any sharding spec), and rebinding the global later "
        "does nothing — or forces a retrace. Per-device data must "
        "arrive as arguments with explicit specs."),
    bad="""\
table = jnp.zeros((50_000, 512))     # module global

def embed(ids):
    return table[ids]                # broadcast into every program

embed_p = jax.pmap(embed)
""",
    good="""\
def embed(table, ids):               # explicit argument
    return table[ids]

embed_p = jax.pmap(embed, in_axes=(None, 0))
""",
    checker=_check_spmd_global_capture))


# ---------------------------------------------------------------------------
# GL009 — broad except swallowing checkpoint / device I/O failures
# ---------------------------------------------------------------------------

# call footprints that mean "this try block does checkpoint or device
# I/O": last dotted segment (methods on managers, jax transfer calls)
# or a bare name (builtins). Tuned to this codebase's idioms — orbax
# manager methods, jax device transfer, raw file handles.
_GL009_IO_ATTRS = {"save", "restore", "restore_latest", "item_metadata",
                   "wait_until_finished", "device_get", "device_put",
                   "block_until_ready", "read_bytes", "write_bytes",
                   "read_text", "write_text"}
_GL009_IO_NAMES = {"open"}
_GL009_IO_PREFIXES = ("ocp.", "orbax.", "jax.device_", "os.")

_GL009_LOG_NAMES = {"print", "log", "warn", "warning", "error", "exception",
                    "debug", "info", "log_step", "log_eval"}

_BROAD_EXC = {"Exception", "BaseException"}


def _gl009_is_broad_handler(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:                                  # bare `except:`
        return True
    if isinstance(t, (ast.Name, ast.Attribute)):
        d = dotted(t)
        return d is not None and d.split(".")[-1] in _BROAD_EXC
    if isinstance(t, ast.Tuple):
        return any(dotted(e) is not None
                   and dotted(e).split(".")[-1] in _BROAD_EXC
                   for e in t.elts)
    return False


def _gl009_io_call(call: ast.Call) -> Optional[str]:
    f = dotted(call.func)
    if f is None:
        if isinstance(call.func, ast.Attribute) \
                and call.func.attr in _GL009_IO_ATTRS:
            return call.func.attr            # method on a computed object
        return None
    last = f.split(".")[-1]
    if last in _GL009_IO_ATTRS or f in _GL009_IO_NAMES:
        return f
    if any(f.startswith(p) for p in _GL009_IO_PREFIXES):
        return f
    return None


def _gl009_handler_swallows(handler: ast.ExceptHandler) -> bool:
    """True when the handler neither re-raises nor logs — the failure
    leaves no trace at all."""
    for node in ast.walk(handler):
        if isinstance(node, ast.Raise):
            return False
        if isinstance(node, ast.Call):
            f = dotted(node.func)
            name = (f.split(".")[-1] if f
                    else getattr(node.func, "attr", ""))
            if name in _GL009_LOG_NAMES:
                return False
    return True


def _check_swallowed_io_except(tree, lines, path):
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Try):
            continue
        io_call = None
        for sub in node.body:
            for c in ast.walk(sub):
                if isinstance(c, ast.Call):
                    io_call = io_call or _gl009_io_call(c)
        if io_call is None:
            continue
        for handler in node.handlers:
            if not _gl009_is_broad_handler(handler):
                continue
            if not _gl009_handler_swallows(handler):
                continue
            findings.append(_finding(
                "GL009", handler,
                f"broad `except` swallows failures of `{io_call}(...)` "
                f"with no re-raise and no log — a corrupt/partial "
                f"checkpoint or failed device transfer disappears here "
                f"and resurfaces later as an unrelated cryptic error; "
                f"catch the narrow exception, or log/re-raise with the "
                f"step and path named",
                path, lines))
    return findings


_register(Rule(
    id="GL009", name="swallowed-io-except",
    rationale=(
        "`except Exception:` (or bare `except:`) around checkpoint or "
        "device I/O that neither re-raises nor logs erases the only "
        "evidence of a half-written checkpoint, a failed device "
        "transfer, or transient storage trouble. The failure then "
        "resurfaces steps later as a cryptic unrelated error — this "
        "package's restore path did exactly that, silently skipping "
        "its RNG-impl check on corrupt checkpoints until the "
        "robustness PR made corruption a named, typed error. Narrow "
        "the exception (OSError for transient I/O, KeyError for "
        "missing metadata) or convert it into a typed error naming "
        "the step."),
    bad="""\
def latest_rng_shape(mngr, step):
    try:
        return mngr.item_metadata(step)["state"]["rng"].shape
    except Exception:        # corrupt step vanishes here
        return None
""",
    good="""\
def latest_rng_shape(mngr, step):
    try:
        return mngr.item_metadata(step)["state"]["rng"].shape
    except (KeyError, TypeError, OSError) as e:
        raise CorruptCheckpointError(
            f"checkpoint step {step} is corrupt: {e}") from e
""",
    checker=_check_swallowed_io_except))


# ---------------------------------------------------------------------------
# GL010–GL014 — mesh/sharding hazard family (project-index passes; the
# implementations live in dataflow.py, next to the call-graph plumbing
# they share with the interprocedural upgrades above)
# ---------------------------------------------------------------------------

_register(Rule(
    id="GL010", name="spec-axis-not-in-mesh",
    rationale=(
        "A PartitionSpec naming an axis the mesh doesn't have is the "
        "silent version of a wrong layout: depending on context GSPMD "
        "either raises at lowering or treats the unknown axis as "
        "replicated — the array LOOKS sharded in the code and is not, "
        "so the program runs, just with a full copy per device and "
        "collectives that don't match the mental model. The pjit/TPUv4 "
        "scaling story is sharding-annotation consistency; this rule "
        "checks the half of it that is statically checkable (meshes "
        "whose axis names are literal)."),
    bad="""\
mesh = Mesh(devices, ("data", "model"))
s = NamedSharding(mesh, P("data", "seq"))   # 'seq' is not a mesh axis
""",
    good="""\
mesh = Mesh(devices, ("data", "seq", "model"))
s = NamedSharding(mesh, P("data", "seq"))   # every axis exists
""",
    project_checker=_project("check_spec_mesh_mismatch")))


_register(Rule(
    id="GL011", name="unsharded-global-in-annotated-program",
    rationale=(
        "A function whose program carries sharding annotations "
        "(in_shardings/out_shardings, shard_map, pjit) that closes over "
        "a module-level array built with plain jnp/np calls embeds that "
        "array OUTSIDE the sharding contract: it is baked into the "
        "program fully replicated on every device. For a lookup table "
        "or mask at model scale that's a full per-device HBM copy no "
        "spec accounts for — the exact waste the annotations were "
        "supposed to rule out."),
    bad="""\
table = jnp.zeros((50_000, 512))              # module scope, no sharding

@partial(jax.jit, in_shardings=(x_sharding,))
def embed(ids):
    return table[ids]                         # replicated capture
""",
    good="""\
@partial(jax.jit, in_shardings=(x_sharding, table_sharding))
def embed(ids, table):                        # explicit, spec'd argument
    return table[ids]
""",
    project_checker=_project("check_unsharded_global_capture")))


_register(Rule(
    id="GL012", name="shardings-arity-mismatch",
    rationale=(
        "in_shardings / in_specs zip positionally against the wrapped "
        "function's arguments (and out_shardings / out_specs against "
        "its returns). A literal tuple of the wrong length either "
        "raises at the first call — or worse, with optional trailing "
        "arguments, quietly shifts every spec onto the wrong parameter "
        "so the batch gets the weights' sharding and vice versa. The "
        "arity is statically checkable whenever the spec tuple is a "
        "literal; this rule checks exactly that and nothing more."),
    bad="""\
@partial(jax.jit, in_shardings=(x_shard, w_shard))
def apply(x, w, b):                  # 3 args, 2 specs: b inherits w's?
    return x @ w + b
""",
    good="""\
@partial(jax.jit, in_shardings=(x_shard, w_shard, b_shard))
def apply(x, w, b):                  # one spec per argument
    return x @ w + b
""",
    project_checker=_project("check_shardings_arity")))


_register(Rule(
    id="GL013", name="varying-scalar-into-shape-arg",
    rationale=(
        "A Python scalar that changes per loop iteration (the loop "
        "variable, a len() of a growing list) flowing into a parameter "
        "a jitted function uses in a shape — or declared static — "
        "compiles a fresh program per distinct value. This is the "
        "recompile-per-length death spiral: the run works at toy sizes "
        "and spends 90% of wall-clock in XLA at real ones. Pad to "
        "fixed buckets (what the serving engine's static slot/window "
        "shapes do) or keep the size a traced array dimension."),
    bad="""\
@partial(jax.jit, static_argnames=("n",))
def window(x, n):
    return x[:n] * jnp.ones((n,))

for i in range(steps):
    out = window(x, i)        # one fresh XLA program per i
""",
    good="""\
@partial(jax.jit, static_argnames=("n",))
def window(x, n):
    return x[:n] * jnp.ones((n,))

BUCKET = 128                  # pad sizes to a fixed bucket: one program
for i in range(steps):
    out = window(x, BUCKET)
""",
    project_checker=_project("check_varying_shape_args")))


_register(Rule(
    id="GL014", name="donated-closure-constant",
    rationale=(
        "Donating a buffer that the jitted body ALSO captures as a "
        "closure constant frees the very memory the compiled program "
        "holds a baked-in reference to: XLA reuses the donated pages "
        "for the output while the constant still points at them. The "
        "first call may even work; later calls read whatever the "
        "output overwrote — silent corruption, not a crash. If the "
        "buffer must be updated in place, pass it as the donated "
        "argument everywhere and drop the capture."),
    bad="""\
state = jnp.zeros((1024,))

@partial(jax.jit, donate_argnames=("s",))
def step(s):
    return s + state              # captures `state` as a constant

out = step(state)                 # ...and donates the same buffer
""",
    good="""\
@partial(jax.jit, donate_argnames=("s",))
def step(s, delta):
    return s + delta              # everything arrives as an argument

state = step(state, delta)
""",
    project_checker=_project("check_donated_closure_capture")))


# ---------------------------------------------------------------------------
# GL015 — host-blocking calls inside the windowed dispatch path
# ---------------------------------------------------------------------------

#: function-name prefixes marking the LAUNCH side of a double-buffered
#: dispatch path (the serving engine's `_launch*` family): code here runs
#: BETWEEN dispatching window N and fetching window N-1, so any blocking
#: fetch forfeits the overlap the whole async design exists to buy
_GL015_LAUNCH_PREFIXES = ("_launch",)
#: calls that force a host<->device sync (or drain the in-flight window)
_GL015_BLOCKING_NAMES = {"np.asarray", "numpy.asarray", "jax.device_get",
                         "jax.block_until_ready"}
_GL015_BLOCKING_ATTRS = {"_drain_pending", "_drain_window",
                         "block_until_ready", "item"}


def _check_windowed_host_block(tree: ast.Module, lines: Sequence[str],
                               path: str) -> List[Finding]:
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not any(node.name.startswith(p)
                   for p in _GL015_LAUNCH_PREFIXES):
            continue
        for call in ast.walk(node):
            if not isinstance(call, ast.Call):
                continue
            f = dotted(call.func)
            hit = None
            if f in _GL015_BLOCKING_NAMES:
                hit = f
            elif (isinstance(call.func, ast.Attribute)
                  and call.func.attr in _GL015_BLOCKING_ATTRS):
                hit = call.func.attr
            if hit is not None:
                findings.append(_finding(
                    "GL015", call,
                    f"`{hit}(...)` inside `{node.name}` — the launch "
                    f"side of a windowed dispatch path must not block "
                    f"on (or drain) the in-flight window: a "
                    f"synchronous fetch here serializes host and "
                    f"device, silently re-creating the blocked "
                    f"step-per-dispatch loop the window path exists "
                    f"to amortize; fetch in the drain-side function "
                    f"(`_drain_window`) after the next window has "
                    f"launched",
                    path, lines))
    return findings


_register(Rule(
    id="GL015", name="windowed-path-host-block",
    rationale=(
        "The async serving engine's launch path (`_launch*`) runs "
        "between dispatching window N and fetching window N-1 — the "
        "host-runs-ahead overlap that amortizes the per-dispatch host "
        "tax (BENCH_r03's 4-5x). A blocking fetch (np.asarray of a "
        "device array, jax.device_get, .block_until_ready(), .item()) "
        "or a `_drain_pending()`/`_drain_window()` call introduced "
        "there serializes host against device on EVERY window and "
        "silently reverts the engine to blocked step-per-dispatch "
        "behavior — no error, no recompile, just the dispatch-split "
        "line quietly collapsing. Continuous windows made admissions, "
        "deadlines and cancels ride the dispatch as masks exactly so "
        "nothing needs to block at launch; keep every sync in the "
        "drain-side function, after the next window is in flight."),
    bad="""\
class Engine:
    def _launch(self, k):
        toks = np.asarray(self._inflight.toks)   # blocks mid-launch
        self._drain_pending()                    # breaks the window
        return self._dispatch(k)
""",
    good="""\
class Engine:
    def _launch(self, k):
        out = self._dispatch(k)      # enqueue only; no device wait
        out.copy_to_host_async()     # overlap the transfer
        return out

    def _drain_window(self, w):
        return np.asarray(w.toks)    # the ONE sync, at the boundary
""",
    checker=_check_windowed_host_block))


# ---------------------------------------------------------------------------
# GL016 — shared-filesystem assumptions on the router side of the fleet
# ---------------------------------------------------------------------------

#: reader calls that imply the caller can see the target file
_GL016_READERS = {"open", "load_jsonl_if_exists",
                  "RequestJournal.unfinished"}
#: attribute/name spellings of PER-WORKER artifact paths: a router
#: holding one of these and reading through it assumes the worker's
#: disk is mounted here
_GL016_PATH_NAMES = {"journal_path", "ready_file"}
#: string literals shaped like per-replica artifacts: flat
#: replica{i}.jsonl / worker{i}.jsonl names, the per-worker-dir
#: layout worker{i}/journal.jsonl, and ready files
_GL016_PATH_LITERAL = re.compile(
    r"(?:replica|worker)\d*[^/]*\.jsonl$"
    r"|(?:^|/)worker\d*/journal\.jsonl$"
    r"|\.ready(?:\.json)?$")


def _gl016_class_is_local(node: ast.ClassDef) -> bool:
    """A class declaring ``is_local = True`` at class level is the
    local-mode backend: its replica shares the router's filesystem by
    construction, so reading its own journal path is legitimate."""
    for stmt in node.body:
        if isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                if (isinstance(t, ast.Name) and t.id == "is_local"
                        and isinstance(stmt.value, ast.Constant)
                        and stmt.value.value is True):
                    return True
    return False


def _gl016_worker_path_arg(call: ast.Call) -> Optional[str]:
    for arg in list(call.args) + [kw.value for kw in call.keywords]:
        for n in ast.walk(arg):
            if (isinstance(n, ast.Attribute)
                    and n.attr in _GL016_PATH_NAMES):
                return n.attr
            if isinstance(n, ast.Name) and n.id in _GL016_PATH_NAMES:
                return n.id
            if (isinstance(n, ast.Constant)
                    and isinstance(n.value, str)
                    and _GL016_PATH_LITERAL.search(n.value)):
                return repr(n.value)
    return None


def _check_fleet_shared_fs(tree: ast.Module, lines: Sequence[str],
                           path: str) -> List[Finding]:
    findings: List[Finding] = []

    def visit(node: ast.AST, exempt: bool) -> None:
        if isinstance(node, ast.ClassDef):
            exempt = exempt or _gl016_class_is_local(node)
        if isinstance(node, ast.Call) and not exempt:
            f = dotted(node.func)
            is_reader = (f in _GL016_READERS
                         or (isinstance(node.func, ast.Attribute)
                             and node.func.attr == "unfinished"))
            if is_reader:
                hit = _gl016_worker_path_arg(node)
                if hit is not None:
                    findings.append(_finding(
                        "GL016", node,
                        f"`{f or node.func.attr}(...)` reads a "
                        f"per-worker artifact ({hit}) on the router "
                        f"side of the fleet — a shared-filesystem "
                        f"assumption: the worker's disk may be on "
                        f"another machine (or gone entirely, the "
                        f"host-loss case). Reconcile through the "
                        f"backend's `journal_state()` (journal_drain "
                        f"RPC for remote replicas) or the router's "
                        f"own ledger; only the local-mode backend "
                        f"(`is_local = True`) may touch a replica "
                        f"path directly",
                        path, lines))
        for child in ast.iter_child_nodes(node):
            visit(child, exempt)

    visit(tree, False)
    return findings


_register(Rule(
    id="GL016", name="fleet-shared-filesystem",
    rationale=(
        "The multi-host fleet's contract is that NO component reads "
        "another component's disk: workers journal locally, the "
        "router journals its own ledger, and reconciliation state "
        "crosses the RPC channel (register handshake, journal_drain "
        "frames). Router-side code that opens a worker's journal or "
        "a ready file works perfectly on one machine and silently "
        "pins the whole fleet to one filesystem — the moment a worker "
        "lands on another host (or its host vanishes, taking the "
        "journal with it), recovery reads an empty/missing file and "
        "requests are dropped or double-decoded. The in-process "
        "backend (`is_local = True`) is exempt: its replica shares "
        "the router's filesystem by construction."),
    bad="""\
class Router:
    def reconcile(self, rep):
        # the worker's journal may live on ANOTHER MACHINE
        return RequestJournal.unfinished(rep.journal_path)

    def await_worker(self, spec):
        with open(spec.ready_file) as f:   # ready-file handshake
            return json.load(f)
""",
    good="""\
class Replica:
    is_local = True                        # in-process: same disk

    def journal_state(self):
        return RequestJournal.unfinished(self.journal_path)

class Router:
    def reconcile(self, rep):
        # the BACKEND owns journal access: local file or
        # journal_drain RPC — the router never sees a path
        return rep.journal_state()
""",
    checker=_check_fleet_shared_fs))


# ---------------------------------------------------------------------------
# GL017 — dtype drift: implicit upcasts in kernel bodies, uncast pool writes
# ---------------------------------------------------------------------------

#: a function whose parameter list carries this many ``*_ref`` names is
#: treated as a Pallas kernel body (the convention every kernel in
#: ops/ follows)
_GL017_MIN_REF_PARAMS = 2
#: root names of KV-pool-shaped arrays a scatter/dynamic_update_slice
#: may write into: the paged pool arrays (ck/cv), their quantization
#: scale arrays (cks/cvs), and anything called cache/pool
_GL017_POOL_NAME = re.compile(r"^(c[kv]s?|cc|cache|.*pool.*)$")


def _gl017_is_kernel_body(fn) -> bool:
    args = fn.args
    names = [a.arg for a in (args.posonlyargs + args.args
                             + args.kwonlyargs)]
    if args.vararg is not None:
        names.append(args.vararg.arg)
    return sum(n.endswith("_ref") for n in names) >= _GL017_MIN_REF_PARAMS


def _gl017_ref_load(node) -> Optional[str]:
    """The ``name_ref[...]`` spelling of a raw ref load, or None."""
    if (isinstance(node, ast.Subscript)
            and isinstance(node.value, ast.Name)
            and node.value.id.endswith("_ref")):
        return node.value.id
    return None


def _gl017_is_astype_call(node) -> bool:
    return (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "astype")


def _gl017_pool_root(node) -> Optional[str]:
    """Root NAME of a pool-shaped write target: ``ck``, ``cache["k"]``
    (root ``cache``), ... — None when the base is not a plain name or
    does not look pool-shaped."""
    base = node
    while isinstance(base, ast.Subscript):
        base = base.value
    if isinstance(base, ast.Name) and _GL017_POOL_NAME.match(base.id):
        return base.id
    return None


def _gl017_value_casts_to_target_dtype(value: ast.AST) -> bool:
    """True when the written value contains an ``.astype(<x>.dtype)``
    call — the explicit store-dtype cast every pool write must carry."""
    for n in ast.walk(value):
        if _gl017_is_astype_call(n) and n.args:
            for a in ast.walk(n.args[0]):
                if isinstance(a, ast.Attribute) and a.attr == "dtype":
                    return True
    return False


def _check_dtype_drift(tree: ast.Module, lines: Sequence[str],
                       path: str) -> List[Finding]:
    findings: List[Finding] = []
    # half 1: implicit upcasts in Pallas kernel bodies — a raw
    # ``x_ref[...]`` load mixed with an explicitly-cast operand in one
    # arithmetic expression promotes by the REF's (implicit) dtype
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not _gl017_is_kernel_body(node):
            continue
        for op in ast.walk(node):
            if not isinstance(op, ast.BinOp):
                continue
            sides = (op.left, op.right)
            for raw, cast in (sides, sides[::-1]):
                ref = _gl017_ref_load(raw)
                if ref is not None and _gl017_is_astype_call(cast):
                    findings.append(_finding(
                        "GL017", op,
                        f"raw `{ref}[...]` load mixed with an "
                        f"explicitly-cast operand in one expression "
                        f"inside kernel body `{node.name}` — the "
                        f"result dtype silently follows the ref's "
                        f"storage dtype (an int8/bf16 pool block "
                        f"upcasts or truncates here without a trace); "
                        f"bind the load to a name with an explicit "
                        f"`.astype(...)` first so the compute "
                        f"precision is visible at the use site",
                        path, lines))
                    break
    # half 2: mixed-dtype scatter / dynamic_update_slice writes into
    # pool-shaped arrays — quantized pools made the store dtype (int8/
    # fp8 rows, f32 scales) diverge from the compute dtype, so an
    # uncast write either promotes the whole pool buffer or silently
    # rounds through the wrong dtype
    for call in ast.walk(tree):
        if not isinstance(call, ast.Call):
            continue
        target = value = None
        f = dotted(call.func)
        if (isinstance(call.func, ast.Attribute)
                and call.func.attr in ("set", "add")
                and isinstance(call.func.value, ast.Subscript)
                and isinstance(call.func.value.value, ast.Attribute)
                and call.func.value.value.attr == "at"):
            # <target>.at[...].set(value)
            target = _gl017_pool_root(call.func.value.value.value)
            value = call.args[0] if call.args else None
        elif f in ("jax.lax.dynamic_update_slice",
                   "lax.dynamic_update_slice",
                   "dynamic_update_slice") and len(call.args) >= 2:
            target = _gl017_pool_root(call.args[0])
            value = call.args[1]
            # ONE exemption, for this spelling only: a bare-name value
            # into dynamic_update_slice is the COW page-copy idiom
            # (re-writing a slice OF the same pool — the dtype is
            # carried by construction). Scatter writes get no such
            # pass: `.at[...].set(k_m)` is the uncast fresh-row write
            # the rule exists to flag.
            if isinstance(value, ast.Name):
                continue
        if target is None or value is None:
            continue
        if not _gl017_value_casts_to_target_dtype(value):
            findings.append(_finding(
                "GL017", call,
                f"write into pool-shaped array `{target}` without an "
                f"explicit `.astype({target}.dtype)` on the value — "
                f"with quantized pools the store dtype (int8/fp8 rows, "
                f"f32 scales) differs from the compute dtype, and an "
                f"uncast scatter either type-promotes the whole pool "
                f"buffer (silent 2-4x HBM regression) or rounds "
                f"through the wrong dtype; cast the value to the "
                f"target's dtype at the write site",
                path, lines))
    return findings


_register(Rule(
    id="GL017", name="dtype-drift",
    rationale=(
        "Quantized KV pools (quant/) store int8/fp8 rows next to f32 "
        "scale arrays while compute runs in bf16/f32 — the one place "
        "in the codebase where three dtypes meet in a single "
        "expression. Two silent failure shapes: (1) inside a Pallas "
        "kernel body, a raw `x_ref[...]` load mixed into an "
        "expression whose other operand is explicitly `.astype(...)`-"
        "cast promotes by the ref's STORAGE dtype — an int8 page "
        "block scores attention in int arithmetic, or a bf16 block "
        "silently upcasts per element instead of once; (2) a scatter "
        "or dynamic_update_slice into a pool-shaped array whose value "
        "lacks `.astype(<target>.dtype)` relies on implicit casting — "
        "under type promotion the WRITE can promote the whole pool "
        "buffer (a silent 2-4x HBM regression), and with a quantized "
        "pool it rounds through the wrong dtype without an error. "
        "Both are one explicit cast away from unambiguous."),
    bad="""\
def _my_kernel(q_ref, kp_ref, out_ref, *, scale):
    # raw int8 ref load mixed with a cast operand: implicit upcast
    s = kp_ref[...] * q_ref[...].astype(jnp.float32)
    out_ref[...] = s

def write(ck, k_m, layer, phys, woff):
    # uncast scatter into the pool: promotes or mis-rounds the buffer
    return ck.at[layer, phys, woff, :].set(k_m, mode="drop")
""",
    good="""\
def _my_kernel(q_ref, kp_ref, out_ref, *, scale):
    kc = kp_ref[...].astype(jnp.float32)     # precision visible here
    s = kc * q_ref[...].astype(jnp.float32)
    out_ref[...] = s.astype(out_ref.dtype)

def write(ck, k_m, layer, phys, woff):
    return ck.at[layer, phys, woff, :].set(
        k_m.astype(ck.dtype), mode="drop")   # store dtype explicit
""",
    checker=_check_dtype_drift))


# ---------------------------------------------------------------------------
# GL018–GL023 — distributed-protocol & async-concurrency family (v3).
# All six are project_checker-only: the contracts they check (wire
# codecs, forwarding whitelists, metric schemas, trace pins) span files
# by construction. dataflow.py hosts the callgraph-walking pair
# (GL019/GL020); contracts.py hosts the contract-registry four.
# ---------------------------------------------------------------------------


_register(Rule(
    id="GL018", name="rpc-verb-contract",
    rationale=(
        "The fleet RPC wire protocol is JSON dicts over a framed "
        "socket: nothing type-checks the verb names or the per-verb "
        "request/response keys, so a key renamed on one side of the "
        "router/worker boundary fails at RUNTIME on the other — as a "
        "worker-side KeyError that downs the replica, or worse, a "
        "``.get()`` default silently zeroing a field every wire "
        "crossing (the drift class every fleet PR since PR 13 fixed "
        "by hand at review). Both sides are literal AST structure: "
        "``op_<verb>`` handlers on dispatch classes read "
        "``doc[\"k\"]`` (required) / ``doc.get(\"k\")`` or "
        "branch-guarded keys (optional) and return literal dicts; "
        "call sites name the verb and keys literally. The rule "
        "cross-checks verb existence in both directions, sent-vs-read "
        "request keys, caller reads vs returned response keys, and "
        "``<stem>_to_wire``/``<stem>_from_wire`` codec pairs. A "
        "``**spread`` on either side opens that set (no guessing); "
        "the checks engage only when a dispatch class or codec pair "
        "exists in the linted project."),
    bad="""\
class Worker:
    def dispatch(self, doc):
        return getattr(self, "op_" + doc.get("op"))(doc)
    def op_submit(self, doc):
        req = doc["req"]                     # required key
        return {"accepted": True}
    def op_drain(self, doc):                 # no caller anywhere: dead verb
        return {}

class Client:
    def __init__(self, call):
        self.call = call
    def submit(self, req):
        resp = self.call("submit", payload=req)   # sends 'payload',
        return resp["rejection"]                  # reads a key never returned
""",
    good="""\
class Worker:
    def dispatch(self, doc):
        return getattr(self, "op_" + doc.get("op"))(doc)
    def op_submit(self, doc):
        req = doc["req"]
        if not req:
            return {"accepted": False, "rejection": "empty"}
        return {"accepted": True}

class Client:
    def __init__(self, call):
        self.call = call
    def submit(self, req):
        resp = self.call("submit", req=req, timeout_s=1.0)
        if not resp["accepted"]:
            return resp["rejection"]
        return None
""",
    project_checker=_project("check_rpc_verb_contract")))


_register(Rule(
    id="GL019", name="async-blocking-call",
    rationale=(
        "The serving front door and the worker host are "
        "single-threaded asyncio loops: ONE blocking call inside any "
        "coroutine stalls every concurrent request, every /healthz "
        "probe, and every SSE heartbeat simultaneously (the PR 9 "
        "``/healthz`` hang was exactly this — a liveness probe stuck "
        "behind a sick worker's socket). Blocking hides behind "
        "helpers, so the check is interprocedural: socket "
        "``.recv()``, ``os.fsync``, ``time.sleep``, subprocess "
        "calls, and RPC ``.call(\"verb\", ...)`` sites with no "
        "explicit ``timeout_s`` budget are blocking sites, and any "
        "``async def`` that reaches one through sync calls — "
        "including through receiver types and abstract bases like "
        "``rep.submit(...)`` via ReplicaBase — is flagged at its "
        "call site with the full chain. Awaited calls never count "
        "(they yield), and a reviewed ``# graftlint: disable=GL019`` "
        "at the blocking site blesses every caller: use it for sites "
        "whose blocking is budgeted by construction (a socket under "
        "``settimeout``, deliberate chaos injection)."),
    bad="""\
import time

class Poller:
    def _backoff(self):
        time.sleep(0.5)                  # blocks the event loop

    async def tick(self, client):
        self._backoff()                  # reached from async def
        return client.call("health")     # untimed RPC: unbounded stall
""",
    good="""\
import asyncio

class Poller:
    async def tick(self, client, loop):
        await asyncio.sleep(0.5)         # yields instead of blocking
        return await loop.run_in_executor(
            None, lambda: client.call("health", timeout_s=1.0))
""",
    project_checker=_project("check_async_blocking_call")))


_register(Rule(
    id="GL020", name="unledgered-finish",
    rationale=(
        "Exactly-once delivery across crashes hangs on ONE seam: "
        "every terminal result must route through the crash ledger's "
        "``record_finish`` before (or with) its delivery-map store. "
        "A finish path that stores ``self.results[...]`` without the "
        "ledger write works perfectly until the next crash recovery, "
        "when the journal replays the request it never saw finish — "
        "double-delivering its stream to the client (the PR 13 "
        "ledger exists precisely to prevent this). The rule arms on "
        "classes that own a ``self.ledger``/``self.journal`` and "
        "flags any method storing into ``self.results`` without a "
        "``record_finish`` call in the same method."),
    bad="""\
class MiniRouter:
    def __init__(self, journal):
        self.journal = journal
        self.results = {}

    def on_finish(self, res):
        self.results[res.id] = res       # crash-recovery will resurrect it
""",
    good="""\
class MiniRouter:
    def __init__(self, journal):
        self.journal = journal
        self.results = {}

    def on_finish(self, res):
        if self.journal is not None:
            self.journal.record_finish(res.id, res.finish_reason)
        self.results[res.id] = res       # ledger first, then delivery
""",
    project_checker=_project("check_unledgered_finish")))


_register(Rule(
    id="GL021", name="counter-schema-drift",
    rationale=(
        "Dashboards and alerts index Prometheus counters BY NAME, and "
        "``Metrics.inc`` creates counters on first increment — so a "
        "counter absent from the pinned exposition schema "
        "(``PROM_PINNED_COUNTERS`` in utils/telemetry.py) reads as "
        "'no data' instead of 0 until its first event, which for "
        "failure counters is exactly when you needed the alert to "
        "have been armed. Drift goes both ways: an increment outside "
        "the pinned schema (a new fleet_* counter nobody pinned), "
        "and a pinned name no code path increments (a rename that "
        "left the schema behind — the exposition advertises a metric "
        "that can never move). Literal and resolvable-constant "
        "increment names check exactly; ``\"prefix_\" + reason`` "
        "increments match pins by prefix; a fully dynamic "
        "``inc(k)`` anywhere disables the never-incremented "
        "direction (it could increment anything). Skipped entirely "
        "when the linted project has no pins tuple."),
    bad="""\
PROM_PINNED_COUNTERS = (
    "fleet_requests_routed",
    "fleet_requeue_retries",             # nothing increments this
)

def step(metrics):
    metrics.inc("fleet_requests_routed")
    metrics.inc("fleet_replica_downs")   # incremented but not pinned
""",
    good="""\
PROM_PINNED_COUNTERS = (
    "fleet_requests_routed",
    "fleet_replica_downs",
)

def step(metrics):
    metrics.inc("fleet_requests_routed")
    metrics.inc("fleet_replica_downs")
    metrics.inc("engine_steps")          # outside the pinned families: fine
""",
    project_checker=_project("check_counter_schema_drift")))


_register(Rule(
    id="GL022", name="forwarded-flag-drift",
    rationale=(
        "``serve --multiproc`` respawns workers by RECONSTRUCTING the "
        "command line from the ``ENGINE_FORWARD_FLAGS`` / "
        "``ENGINE_FORWARD_SWITCHES`` whitelists — an ``EngineConfig`` "
        "knob the whitelist doesn't carry means a fleet of workers "
        "silently serving a DIFFERENT engine shape (pool, pages, "
        "decode window, mesh slice) than the operator asked for: the "
        "exact bug class PR 9's review caught by hand. Three drift "
        "directions, all literal AST: a builder keyword whose "
        "``args.<dest>`` read no whitelist entry carries; an "
        "``EngineConfig`` field the builder never passes (the flag "
        "surface cannot express it at all); and a stale whitelist "
        "row whose dest the builder no longer reads. The "
        "``MODEL_OVERRIDE_FLAGS`` dests are checked against "
        "``ModelConfig``'s fields the same way. Skipped when the "
        "linted project has no whitelist assignment."),
    bad="""\
ENGINE_FORWARD_FLAGS = (
    ("pool_size", "--pool-size"),
    ("stale_knob", "--stale-knob"),      # builder never reads it
)

class EngineConfig:
    pool_size: int = 8
    max_queue: int = 64
    page_size: int = 0                   # never passed: inexpressible

def engine_config_from_args(args):
    return EngineConfig(pool_size=args.pool_size,
                        max_queue=args.max_queue)   # not whitelisted
""",
    good="""\
ENGINE_FORWARD_FLAGS = (
    ("pool_size", "--pool-size"),
    ("max_queue", "--max-queue"),
    ("page_size", "--page-size"),
)

class EngineConfig:
    pool_size: int = 8
    max_queue: int = 64
    page_size: int = 0

def engine_config_from_args(args):
    return EngineConfig(pool_size=args.pool_size,
                        max_queue=args.max_queue,
                        page_size=args.page_size)
""",
    project_checker=_project("check_forwarded_flag_drift")))


_register(Rule(
    id="GL023", name="telemetry-span-contract",
    rationale=(
        "``tools/trace_check.py`` validates exported Chrome traces "
        "against named event envelopes (``TRACE_VALIDATED_NAMES``): "
        "request begin/end pairing, page_transfer spans, token "
        "instants, thread_name metadata. The validator and the "
        "emitters drift independently — a span renamed at the "
        "emission site leaves the validator pinning a name nothing "
        "emits, so ``check_trace`` either rejects every healthy "
        "trace or (worse) the validation goes dead and the soak "
        "gate stops checking anything. The rule collects every "
        "literal or constant-resolvable name passed to "
        "``begin/end/instant/complete/span/phase/name_track`` and every "
        "``{\"ph\": ..., \"name\": ...}`` event literal, and flags "
        "pinned names with no emission site. Skipped when the "
        "linted project has no pins tuple."),
    bad="""\
TRACE_VALIDATED_NAMES = ("request", "token", "page_transfer")

def emit(t, track, rid):
    t.begin("request", track, id=rid)
    t.instant("token", track, index=0)   # 'page_transfer' never emitted
""",
    good="""\
TRACE_VALIDATED_NAMES = ("request", "token")

def emit(t, track, rid):
    t.begin("request", track, id=rid)
    t.instant("token", track, index=0)
    t.end("request", track)
""",
    project_checker=_project("check_telemetry_span_contract")))


_register(Rule(
    id="GL024", name="idempotent-mutating-verbs",
    rationale=(
        "Every retry ladder in the fleet is a duplicate-delivery "
        "generator: the router re-sends after a protocol error, a "
        "worker blind-retries registration when the response is "
        "lost, and netchaos (faults/netchaos.py) duplicates frames "
        "outright. A MUTATING verb (``RPC_MUTATING_VERBS`` in "
        "analysis/contracts.py: submit, page_transfer, "
        "journal_drain, register) that re-executes under any of "
        "these double-decodes a request, double-appends staged KV "
        "pages, or reconciles an attach twice — the exactly-once "
        "promise dies at the wire. The contract has three legs, "
        "all literal AST: the verb is declared in a module-global "
        "``*IDEMPOTENT*`` tuple next to its dispatch class; the "
        "dispatch/handler consults an idem-keyed reply cache (reads "
        "``'idem'`` and touches a ``*replies*`` attribute) so a "
        "duplicated call returns the cached reply; and every "
        "literal call site sends an explicit ``idem`` key. Skipped "
        "when the linted files contain no handler for a mutating "
        "verb."),
    bad="""\
class WorkerStub:
    def dispatch(self, doc):
        op = doc.get("op")
        fn = getattr(self, "op_" + op, None)
        if fn is None:
            raise ValueError(op)
        return fn(doc)          # no reply cache, no idem read

    def op_submit(self, doc):   # mutating: enqueues a request
        req = doc["req"]
        return {"accepted": bool(req)}

class ClientStub:
    def __init__(self, call):
        self.call = call

    def submit(self, req):
        # no idem key: a duplicated frame re-enqueues the request
        resp = self.call("submit", req=req, timeout_s=1.0)
        return resp["accepted"]
""",
    good="""\
IDEMPOTENT_VERBS = ("submit",)

class WorkerStub:
    def __init__(self):
        self._replies = {}

    def dispatch(self, doc):
        op = doc.get("op")
        fn = getattr(self, "op_" + op, None)
        if fn is None:
            raise ValueError(op)
        idem = doc.get("idem")
        if op in IDEMPOTENT_VERBS and idem is not None:
            cached = self._replies.get(idem)
            if cached is not None:
                return {**cached, "idem_hit": True}
        resp = fn(doc)
        if op in IDEMPOTENT_VERBS and idem is not None:
            self._replies[idem] = resp
        return resp

    def op_submit(self, doc):
        req = doc["req"]
        return {"accepted": bool(req)}

class ClientStub:
    def __init__(self, call):
        self.call = call
        self._seq = 0

    def submit(self, req):
        self._seq += 1
        resp = self.call("submit", req=req, timeout_s=1.0,
                         idem="sub.%d" % self._seq)
        return resp["accepted"]
""",
    project_checker=_project("check_idempotent_verb_contract")))


def all_rule_ids() -> List[str]:
    return sorted(RULES)
