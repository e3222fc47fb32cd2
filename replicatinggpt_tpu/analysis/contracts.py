"""Project contract registry: statically extracted wire/config/metrics
contracts (graftlint v3).

Every fleet PR since PR 8 shipped post-review fixes for the same drift
classes: a wire-codec key written on one side and never read on the
other, a new :class:`EngineConfig` knob the ``--multiproc`` forwarding
whitelist silently drops, a counter incremented in code but missing
from the pinned Prometheus exposition, a telemetry span the trace
validator expects but nothing emits. None of these need execution to
detect — both sides of each contract are literal structure in the AST.
This module extracts the contracts and checks them:

- **RPC verbs** (GL018): ``op_<verb>`` handler methods on classes that
  also define ``dispatch`` (serve/worker.py), vs every literal
  ``.call("verb", ...)`` / ``._call("verb", ...)`` site
  (serve/router.py, serve/disagg.py, serve/procsup.py). Per verb the
  handler's required (top-level ``doc["k"]``) and optional
  (``doc.get("k")``, or any read under a branch) request keys, and the
  union of its literal response-dict keys, checked against the keys
  each call site sends and the keys callers read off the response.
  Plus the ``<stem>_to_wire`` / ``<stem>_from_wire`` codec pairs:
  a key one direction writes and the other never reads is drift.
- **Forwarded flags** (GL022): ``ENGINE_FORWARD_FLAGS`` /
  ``ENGINE_FORWARD_SWITCHES`` / ``MODEL_OVERRIDE_FLAGS`` whitelists vs
  the ``args.<dest>`` reads of the ``EngineConfig(...)`` builder and
  the field sets of the config classes themselves.
- **Counter schema** (GL021): literal ``Metrics.inc`` names in the
  pinned counter families vs the ``PROM_PINNED_COUNTERS`` exposition
  schema (utils/telemetry.py).
- **Telemetry spans** (GL023): names ``tools/trace_check.py`` pins in
  ``TRACE_VALIDATED_NAMES`` vs the span/instant/meta names the code
  actually emits.

Conservatism contract (same as callgraph.py / dataflow.py): checks fire
on *resolved literal* facts only. A ``**spread`` into a response dict,
a dynamically computed counter name, or a verb behind a variable makes
that side of the contract open — the check skips rather than guesses.
Each rule also skips entirely when its registry anchor (a dispatch
class, a whitelist assignment, the pins tuple) is absent from the
project, so one-file lints of unrelated modules stay quiet.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .callgraph import ModuleInfo, ProjectIndex, dotted
from .rules import Finding

#: kwargs a call site may pass that are transport envelope, not payload
#: ("idem" / "gen" are consumed by the dispatch layer — the idempotency
#: reply cache and the generation fence — never by op_ handlers)
_TRANSPORT_KEYS = {"timeout_s", "idem", "gen"}
_RPC_CALL_ATTRS = {"call", "_call"}


def _line_of(node: ast.AST, lines: Sequence[str]) -> str:
    i = getattr(node, "lineno", 1) - 1
    return lines[i].strip() if 0 <= i < len(lines) else ""


def _finding(rule_id: str, node: ast.AST, message: str, mod: ModuleInfo,
             ) -> Finding:
    return Finding(path=mod.label, rule=rule_id,
                   line=getattr(node, "lineno", 1),
                   col=getattr(node, "col_offset", 0), message=message,
                   text=_line_of(node, mod.lines))


def _const_str(node: Optional[ast.expr]) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _resolve_str(mod: ModuleInfo, idx: ProjectIndex,
                 node: ast.expr, depth: int = 0) -> Optional[str]:
    """A literal string, or a Name that resolves (through module
    globals and one import hop) to one."""
    s = _const_str(node)
    if s is not None:
        return s
    if not isinstance(node, ast.Name) or depth > 2:
        return None
    g = mod.globals.get(node.id)
    if g is not None:
        return _resolve_str(mod, idx, g, depth + 1)
    b = mod.imports.get(node.id)
    if b is not None and b.symbol is not None:
        other = idx.module_for(b.module)
        if other is not None and b.symbol in other.globals:
            return _const_str(other.globals[b.symbol])
    return None


def _fmt(keys: Set[str]) -> str:
    return ", ".join(repr(k) for k in sorted(keys))


# --------------------------------------------------------------------------
# GL018 — RPC verb / wire-key contracts
# --------------------------------------------------------------------------


@dataclass
class VerbContract:
    """One ``op_<verb>`` handler's statically visible wire shape."""

    verb: str
    mod: ModuleInfo = None
    node: ast.AST = None          # the handler FunctionDef
    required: Set[str] = field(default_factory=set)
    optional: Set[str] = field(default_factory=set)
    response: Set[str] = field(default_factory=set)
    response_open: bool = False   # **spread / non-literal return seen


@dataclass
class CallSiteInfo:
    """One literal ``.call("verb", ...)`` site."""

    verb: str
    mod: ModuleInfo = None
    node: ast.Call = None
    sent: Set[str] = field(default_factory=set)
    #: every kwarg at the site INCLUDING transport-envelope keys —
    #: GL024 audits the envelope ("idem" present on mutating verbs)
    #: that GL018's payload view deliberately excludes
    sent_all: Set[str] = field(default_factory=set)
    sent_open: bool = False       # **spread at the call
    #: name the response is bound to (``resp = self._call(...)``), when
    #: the site is the sole value of a simple assignment
    bound_name: Optional[str] = None
    #: enclosing function AST, for the response-read scan
    fn_node: ast.AST = None


def _scan_handler(fn: ast.FunctionDef, doc_param: str) -> VerbContract:
    c = VerbContract(verb="")

    def scan(node: ast.AST, branch_depth: int) -> None:
        for child in ast.iter_child_nodes(node):
            depth = branch_depth
            if isinstance(child, (ast.If, ast.For, ast.While, ast.Try,
                                  ast.IfExp)):
                depth += 1
            if isinstance(child, ast.Subscript) \
                    and isinstance(child.value, ast.Name) \
                    and child.value.id == doc_param:
                key = _const_str(child.slice)
                if key is not None:
                    (c.optional if depth else c.required).add(key)
            elif isinstance(child, ast.Call) \
                    and isinstance(child.func, ast.Attribute) \
                    and child.func.attr == "get" \
                    and isinstance(child.func.value, ast.Name) \
                    and child.func.value.id == doc_param and child.args:
                key = _const_str(child.args[0])
                if key is not None:
                    c.optional.add(key)
            if isinstance(child, ast.Return) and child.value is not None:
                if isinstance(child.value, ast.Dict):
                    for k in child.value.keys:
                        if k is None:          # ** spread
                            c.response_open = True
                        else:
                            key = _const_str(k)
                            if key is None:
                                c.response_open = True
                            else:
                                c.response.add(key)
                else:
                    c.response_open = True
            scan(child, depth)

    scan(fn, 0)
    c.optional -= c.required
    return c


def _harvest_handlers(idx: ProjectIndex) -> Dict[str, VerbContract]:
    handlers: Dict[str, VerbContract] = {}
    for mod in idx.modules.values():
        for info in mod.classes.values():
            if "dispatch" not in info.methods or info.node is None:
                continue
            for sub in info.node.body:
                if not isinstance(sub, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                    continue
                if not sub.name.startswith("op_"):
                    continue
                params = [a.arg for a in sub.args.args]
                doc_param = params[1] if len(params) > 1 else ""
                c = _scan_handler(sub, doc_param)
                c.verb = sub.name[len("op_"):]
                c.mod, c.node = mod, sub
                handlers[c.verb] = c
    return handlers


def _harvest_call_sites(idx: ProjectIndex) -> List[CallSiteInfo]:
    sites: List[CallSiteInfo] = []
    for mod in idx.modules.values():
        for fn in (*mod.functions.values(), mod.toplevel):
            if fn is None or fn.node is None:
                continue
            bound: Dict[int, str] = {}       # id(call node) -> var name
            for sub in ast.walk(fn.node):
                if isinstance(sub, ast.Assign) and len(sub.targets) == 1 \
                        and isinstance(sub.targets[0], ast.Name) \
                        and isinstance(sub.value, ast.Call):
                    bound[id(sub.value)] = sub.targets[0].id
            for sub in ast.walk(fn.node):
                if not (isinstance(sub, ast.Call)
                        and isinstance(sub.func, ast.Attribute)
                        and sub.func.attr in _RPC_CALL_ATTRS and sub.args):
                    continue
                verb = _const_str(sub.args[0])
                if verb is None:
                    continue
                s = CallSiteInfo(verb=verb, mod=mod, node=sub,
                                 bound_name=bound.get(id(sub)))
                for kw in sub.keywords:
                    if kw.arg is None:
                        s.sent_open = True
                    else:
                        s.sent_all.add(kw.arg)
                        if kw.arg not in _TRANSPORT_KEYS:
                            s.sent.add(kw.arg)
                s.fn_node = fn.node          # for response-read scan
                sites.append(s)
    return sites


def _response_reads(fn_node: ast.AST, var: str) -> Set[str]:
    """Literal keys read off ``var`` anywhere in the function:
    ``var["k"]``, ``var.get("k")``, ``"k" in var``."""
    reads: Set[str] = set()
    for sub in ast.walk(fn_node):
        if isinstance(sub, ast.Subscript) \
                and isinstance(sub.value, ast.Name) \
                and sub.value.id == var:
            k = _const_str(sub.slice)
            if k is not None:
                reads.add(k)
        elif isinstance(sub, ast.Call) \
                and isinstance(sub.func, ast.Attribute) \
                and sub.func.attr == "get" \
                and isinstance(sub.func.value, ast.Name) \
                and sub.func.value.id == var and sub.args:
            k = _const_str(sub.args[0])
            if k is not None:
                reads.add(k)
        elif isinstance(sub, ast.Compare) and len(sub.ops) == 1 \
                and isinstance(sub.ops[0], ast.In) \
                and isinstance(sub.comparators[0], ast.Name) \
                and sub.comparators[0].id == var:
            k = _const_str(sub.left)
            if k is not None:
                reads.add(k)
    return reads


def _dict_literal_keys(fn: ast.FunctionDef) -> Tuple[Set[str], bool]:
    """Union of literal dict keys returned by ``fn`` (wire writers
    return one dict literal; comprehensions / spreads open the set)."""
    keys: Set[str] = set()
    open_ = False
    for sub in ast.walk(fn):
        if not isinstance(sub, ast.Return) or sub.value is None:
            continue
        if isinstance(sub.value, ast.Dict):
            for k in sub.value.keys:
                s = _const_str(k) if k is not None else None
                if s is None:
                    open_ = True
                else:
                    keys.add(s)
        else:
            open_ = True
    return keys, open_


def check_rpc_verb_contract(idx: ProjectIndex) -> List[Finding]:
    findings: List[Finding] = []
    handlers = _harvest_handlers(idx)
    sites = _harvest_call_sites(idx)

    if handlers and sites:
        called_verbs = {s.verb for s in sites}
        for verb, h in sorted(handlers.items()):
            if verb not in called_verbs:
                findings.append(_finding(
                    "GL018", h.node,
                    f"RPC handler `op_{verb}` has no literal "
                    f".call({verb!r}, ...) site anywhere in the project — "
                    f"either the client codec was never wired or the verb "
                    f"is dead; every dispatched verb needs a caller",
                    h.mod))
    if handlers:
        for s in sites:
            h = handlers.get(s.verb)
            if h is None:
                findings.append(_finding(
                    "GL018", s.node,
                    f".call({s.verb!r}, ...) has no `op_{s.verb}` handler "
                    f"on any dispatch class — the worker will raise "
                    f"`unknown op` at runtime",
                    s.mod))
                continue
            missing = h.required - s.sent
            if missing and not s.sent_open:
                findings.append(_finding(
                    "GL018", s.node,
                    f".call({s.verb!r}, ...) omits key(s) "
                    f"{_fmt(missing)} that `op_{s.verb}` reads "
                    f"unconditionally — a guaranteed KeyError on the "
                    f"worker", s.mod))
            unknown = s.sent - h.required - h.optional
            if unknown:
                findings.append(_finding(
                    "GL018", s.node,
                    f".call({s.verb!r}, ...) sends key(s) "
                    f"{_fmt(unknown)} that `op_{s.verb}` never reads — "
                    f"dead wire weight, or a key rename that only "
                    f"landed on one side", s.mod))
            if s.bound_name and not h.response_open:
                reads = _response_reads(s.fn_node, s.bound_name)
                ghost = reads - h.response
                if ghost:
                    findings.append(_finding(
                        "GL018", s.node,
                        f"caller reads key(s) {_fmt(ghost)} off the "
                        f"{s.verb!r} response, but `op_{s.verb}` never "
                        f"returns them", s.mod))

    # ---- <stem>_to_wire / <stem>_from_wire codec pairs ------------------
    for mod in idx.modules.values():
        for name, fn in sorted(mod.functions.items()):
            if not name.endswith("_to_wire") or "." in name:
                continue
            stem = name[: -len("_to_wire")]
            reader = mod.functions.get(f"{stem}_from_wire")
            if reader is None or reader.node is None or fn.node is None:
                continue
            writes, w_open = _dict_literal_keys(fn.node)
            if not reader.params:
                continue
            rc = _scan_handler(reader.node, reader.params[0])
            reads = rc.required | rc.optional
            if not w_open:
                for k in sorted(reads - writes):
                    findings.append(_finding(
                        "GL018", reader.node,
                        f"`{stem}_from_wire` reads {k!r} but "
                        f"`{stem}_to_wire` never writes it — the decoded "
                        f"object silently gets the fallback default on "
                        f"every wire crossing", mod))
                for k in sorted(writes - reads):
                    findings.append(_finding(
                        "GL018", fn.node,
                        f"`{stem}_to_wire` writes {k!r} but "
                        f"`{stem}_from_wire` never reads it — dead wire "
                        f"weight, or a reader-side key that drifted",
                        mod))
    return findings


# --------------------------------------------------------------------------
# GL024 — mutating RPC verbs must be idempotent
# --------------------------------------------------------------------------

#: The fleet's MUTATING verbs: their handlers change worker/supervisor
#: state, and every retry ladder in the fleet (router retry-once on
#:  protocol errors, blind re-registration, netchaos duplicates) can
#: deliver them twice. Each one must (a) be declared in a module-global
#: ``*IDEMPOTENT*`` tuple next to its dispatch class, (b) have its
#: dispatch/handler consult an idem-keyed reply cache (an attribute
#: whose name mentions ``replies``), and (c) carry an explicit ``idem``
#: kwarg at every literal call site. Read-only verbs (step, health,
#: prefix, ...) are exempt — re-executing them is harmless.
RPC_MUTATING_VERBS = ("submit", "page_transfer", "journal_drain",
                      "register")


def _reads_key_literal(node: ast.AST, key: str) -> bool:
    """Whether the subtree reads the literal string ``key`` off any
    mapping (``x["key"]`` / ``x.get("key")`` / ``"key" in x``)."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Subscript) \
                and _const_str(sub.slice) == key:
            return True
        if isinstance(sub, ast.Call) \
                and isinstance(sub.func, ast.Attribute) \
                and sub.func.attr == "get" and sub.args \
                and _const_str(sub.args[0]) == key:
            return True
        if isinstance(sub, ast.Compare) and len(sub.ops) == 1 \
                and isinstance(sub.ops[0], ast.In) \
                and _const_str(sub.left) == key:
            return True
    return False


def _consults_reply_cache(node: ast.AST) -> bool:
    """Whether the subtree touches a reply-cache attribute or name
    (``self._replies`` / ``self._reg_replies`` / ...)."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and "replies" in sub.attr:
            return True
        if isinstance(sub, ast.Name) and "replies" in sub.id:
            return True
    return False


def _idempotent_declared(mod: ModuleInfo, idx: ProjectIndex,
                         ) -> Optional[Set[str]]:
    """The union of verbs declared idempotent by the module's
    ``*IDEMPOTENT*`` tuple globals; None when no such global exists."""
    out: Optional[Set[str]] = None
    for name, val in mod.globals.items():
        if "IDEMPOTENT" not in name.upper():
            continue
        if not isinstance(val, (ast.Tuple, ast.List)):
            continue
        out = out or set()
        out |= {s for s in (_resolve_str(mod, idx, e) for e in val.elts)
                if s is not None}
    return out


def check_idempotent_verb_contract(idx: ProjectIndex) -> List[Finding]:
    findings: List[Finding] = []

    # ---- dispatch classes with op_<mutating-verb> handlers -------------
    handled_verbs: Set[str] = set()
    for mod in idx.modules.values():
        for info in mod.classes.values():
            if "dispatch" not in info.methods or info.node is None:
                continue
            dispatch_fn = None
            mutating = []
            for sub in info.node.body:
                if not isinstance(sub, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                    continue
                if sub.name == "dispatch":
                    dispatch_fn = sub
                elif sub.name.startswith("op_") \
                        and sub.name[len("op_"):] in RPC_MUTATING_VERBS:
                    mutating.append(sub)
            if not mutating:
                continue
            handled_verbs |= {m.name[len("op_"):] for m in mutating}
            declared = _idempotent_declared(mod, idx)
            if declared is None:
                findings.append(_finding(
                    "GL024", info.node,
                    f"dispatch class `{info.name}` handles mutating RPC "
                    f"verb(s) "
                    f"{_fmt({m.name[len('op_'):] for m in mutating})} "
                    f"but its module declares no *IDEMPOTENT* verbs "
                    f"tuple — duplicated or blindly-retried calls will "
                    f"re-execute", mod))
            else:
                for m in mutating:
                    verb = m.name[len("op_"):]
                    if verb not in declared:
                        findings.append(_finding(
                            "GL024", m,
                            f"mutating RPC verb {verb!r} is not in the "
                            f"module's *IDEMPOTENT* verbs tuple — its "
                            f"replies are never cached, so a netchaos "
                            f"duplicate or a protocol-error retry "
                            f"re-executes it", mod))
            if dispatch_fn is not None and not (
                    _reads_key_literal(dispatch_fn, "idem")
                    and _consults_reply_cache(dispatch_fn)):
                findings.append(_finding(
                    "GL024", dispatch_fn,
                    f"`{info.name}.dispatch` handles mutating verb(s) "
                    f"but never consults an idem-keyed reply cache "
                    f"(read doc's 'idem' + a `*replies*` attribute) — "
                    f"idempotency keys sent by callers are ignored",
                    mod))

    # ---- registration-style handlers (no op_ method) -------------------
    for verb in RPC_MUTATING_VERBS:
        if verb in handled_verbs:
            continue
        for mod in idx.modules.values():
            for name, fn in sorted(mod.functions.items()):
                short = name.split(".")[-1]
                if short not in (f"_handle_{verb}", f"handle_{verb}"):
                    continue
                if fn.node is None:
                    continue
                handled_verbs.add(verb)
                if not (_reads_key_literal(fn.node, "idem")
                        and _consults_reply_cache(fn.node)):
                    findings.append(_finding(
                        "GL024", fn.node,
                        f"`{short}` executes the mutating {verb!r} "
                        f"handshake but never consults an idem-keyed "
                        f"reply cache — a worker whose registration "
                        f"response was lost will blind-retry and "
                        f"reconcile twice", mod))

    # ---- call sites: mutating verbs must carry an explicit idem key ----
    if handled_verbs:
        for s in _harvest_call_sites(idx):
            if s.verb not in RPC_MUTATING_VERBS \
                    or s.verb not in handled_verbs:
                continue
            if "idem" not in s.sent_all and not s.sent_open:
                findings.append(_finding(
                    "GL024", s.node,
                    f".call({s.verb!r}, ...) sends no 'idem' key — the "
                    f"handler caches replies by idempotency key, so an "
                    f"unkeyed duplicate of this mutating call "
                    f"re-executes instead of hitting the cache",
                    s.mod))
    return findings


# --------------------------------------------------------------------------
# GL021 — counter vs pinned Prometheus schema
# --------------------------------------------------------------------------

_PINS_NAME = "PROM_PINNED_COUNTERS"


def _pinned_counters(idx: ProjectIndex,
                     ) -> Optional[Tuple[ModuleInfo, ast.expr, List[str]]]:
    for mod in idx.modules.values():
        g = mod.globals.get(_PINS_NAME)
        if g is not None and isinstance(g, (ast.Tuple, ast.List)):
            pins = [s for s in (_resolve_str(mod, idx, e) for e in g.elts)
                    if s is not None]
            return mod, g, pins
    return None


def _inc_name(mod: ModuleInfo, idx: ProjectIndex,
              arg: ast.expr) -> Tuple[Optional[str], Optional[str]]:
    """(literal, prefix) of a counter-name argument; (None, None) means
    fully dynamic (a wildcard that can inc anything)."""
    s = _resolve_str(mod, idx, arg)
    if s is not None:
        return s, None
    if isinstance(arg, ast.BinOp) and isinstance(arg.op, ast.Add):
        left = _resolve_str(mod, idx, arg.left)
        if left is not None:
            return None, left
    if isinstance(arg, ast.JoinedStr) and arg.values:
        head = arg.values[0]
        if isinstance(head, ast.Constant) and isinstance(head.value, str):
            return None, head.value
    return None, None


def check_counter_schema_drift(idx: ProjectIndex) -> List[Finding]:
    pinned = _pinned_counters(idx)
    if pinned is None:
        return []
    pins_mod, pins_node, pins = pinned
    families = {p.split("_", 1)[0] + "_" for p in pins if "_" in p}

    findings: List[Finding] = []
    literals: List[Tuple[ModuleInfo, ast.Call, str]] = []
    prefixes: Set[str] = set()
    saw_wildcard = False
    for mod in idx.modules.values():
        for sub in ast.walk(mod.tree):
            if not (isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Attribute)
                    and sub.func.attr == "inc" and sub.args):
                continue
            lit, pre = _inc_name(mod, idx, sub.args[0])
            if lit is not None:
                literals.append((mod, sub, lit))
            elif pre is not None:
                prefixes.add(pre)
            else:
                saw_wildcard = True

    for mod, node, lit in literals:
        if any(lit.startswith(f) for f in families) and lit not in pins:
            findings.append(_finding(
                "GL021", node,
                f"counter {lit!r} is incremented here but absent from "
                f"{_PINS_NAME} ({pins_mod.label}) — it will not appear "
                f"in the pinned Prometheus exposition until first "
                f"increment, so dashboards and alerts on it silently "
                f"read 'no data' instead of 0", mod))

    # The never-incremented direction needs the incrementing side in
    # scope to judge liveness: a one-file lint of the pins module alone
    # (zero inc sites anywhere) proves nothing, so stay silent there.
    lit_names = {lit for _, _, lit in literals}
    any_inc_site = bool(literals or prefixes or saw_wildcard)
    if any_inc_site and not saw_wildcard:
        for p in pins:
            if p in lit_names:
                continue
            if any(p.startswith(pre) for pre in prefixes):
                continue
            findings.append(_finding(
                "GL021", pins_node,
                f"pinned counter {p!r} is never incremented anywhere — "
                f"the exposition advertises a metric no code path can "
                f"move; delete the pin or wire the increment",
                pins_mod))
    return findings


# --------------------------------------------------------------------------
# GL022 — forwarded-flag whitelists vs config fields
# --------------------------------------------------------------------------

_ENGINE_LISTS = ("ENGINE_FORWARD_FLAGS", "ENGINE_FORWARD_SWITCHES")
_MODEL_LIST = "MODEL_OVERRIDE_FLAGS"


def _dest_pairs(expr: ast.expr) -> List[str]:
    """dests of a ((dest, flag), ...) whitelist literal."""
    out: List[str] = []
    if isinstance(expr, (ast.Tuple, ast.List)):
        for e in expr.elts:
            if isinstance(e, (ast.Tuple, ast.List)) and e.elts:
                d = _const_str(e.elts[0])
                if d is not None:
                    out.append(d)
    return out


def _arg_attr_reads(node: ast.AST, ns_names: Set[str]) -> Set[str]:
    """Attributes read off any of the namespace names inside ``node``."""
    out: Set[str] = set()
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) \
                and isinstance(sub.value, ast.Name) \
                and sub.value.id in ns_names:
            out.add(sub.attr)
    return out


def _class_fields(idx: ProjectIndex, cls_name: str) -> Optional[Set[str]]:
    infos = idx.class_infos(cls_name)
    if not infos:
        return None
    fields: Set[str] = set()
    for _, info in infos:
        if info.node is None:
            continue
        for sub in info.node.body:
            if isinstance(sub, ast.AnnAssign) \
                    and isinstance(sub.target, ast.Name):
                fields.add(sub.target.id)
    return fields or None


def check_forwarded_flag_drift(idx: ProjectIndex) -> List[Finding]:
    findings: List[Finding] = []

    # ---- engine side: whitelists vs the EngineConfig(...) builder -------
    # The contract is deliberately local: ENGINE_FORWARD_FLAGS lives next
    # to the builder that consumes it (cli.py), so only builders in a
    # whitelist-defining module are held to the whitelist.  Ad-hoc
    # EngineConfig(...) constructions elsewhere (bench harnesses, tests)
    # are not part of the multiproc respawn surface.
    lists_mods: List[ModuleInfo] = []
    for mod in idx.modules.values():
        if any(mod.globals.get(l) is not None for l in _ENGINE_LISTS):
            lists_mods.append(mod)

    for lists_mod in lists_mods:
        engine_dests: Set[str] = set()
        list_nodes: List[Tuple[ModuleInfo, str, ast.expr]] = []
        for lname in _ENGINE_LISTS:
            g = lists_mod.globals.get(lname)
            if g is not None:
                engine_dests |= set(_dest_pairs(g))
                list_nodes.append((lists_mod, lname, g))
        for mod in (lists_mod,):
            for fname, fn in sorted(mod.functions.items()):
                if fn.node is None:
                    continue
                ns = {p for p in fn.params}
                for sub in ast.walk(fn.node):
                    if not (isinstance(sub, ast.Call) and sub.keywords):
                        continue
                    d = dotted(sub.func)
                    if d is None or d.split(".")[-1] != "EngineConfig":
                        continue
                    kw_dests: Dict[str, Set[str]] = {}
                    local_reads = _local_name_arg_reads(fn.node, ns)
                    any_arg_read = False
                    for kw in sub.keywords:
                        if kw.arg is None:
                            continue
                        dests = _arg_attr_reads(kw.value, ns)
                        for n in {x.id for x in ast.walk(kw.value)
                                  if isinstance(x, ast.Name)}:
                            dests |= local_reads.get(n, set())
                        if dests:
                            any_arg_read = True
                        kw_dests[kw.arg] = dests
                    if not any_arg_read:
                        continue          # a literal construction, not
                                          # the CLI builder
                    for kw_name, dests in sorted(kw_dests.items()):
                        stray = dests - engine_dests
                        if stray:
                            findings.append(_finding(
                                "GL022", sub,
                                f"EngineConfig field `{kw_name}` is built "
                                f"from args.{'/args.'.join(sorted(stray))} "
                                f"but no ENGINE_FORWARD_FLAGS/_SWITCHES "
                                f"entry carries it — `serve --multiproc` "
                                f"workers respawn WITHOUT this knob and "
                                f"silently serve a different engine shape",
                                mod))
                    fields = _class_fields(idx, "EngineConfig")
                    if fields:
                        for missing in sorted(fields - set(kw_dests)):
                            findings.append(_finding(
                                "GL022", sub,
                                f"EngineConfig field `{missing}` is never "
                                f"passed by this builder — the flag "
                                f"surface cannot express it, so every "
                                f"deployment silently runs the default",
                                mod))
                    used = _arg_attr_reads(fn.node, ns)
                    for mod2, lname, g in list_nodes:
                        for dest in _dest_pairs(g):
                            if dest not in used:
                                findings.append(_finding(
                                    "GL022", g,
                                    f"{lname} entry `{dest}` is not read "
                                    f"by the EngineConfig builder — a "
                                    f"stale whitelist row forwards a flag "
                                    f"the engine no longer consumes",
                                    mod2))

    # ---- model side: MODEL_OVERRIDE_FLAGS dests must be ModelConfig ----
    for mod in idx.modules.values():
        g = mod.globals.get(_MODEL_LIST)
        if g is None:
            continue
        fields = _class_fields(idx, "ModelConfig")
        if not fields:
            continue
        for dest in _dest_pairs(g):
            if dest not in fields:
                findings.append(_finding(
                    "GL022", g,
                    f"{_MODEL_LIST} entry `{dest}` is not a ModelConfig "
                    f"field — the override either crashes replace() or "
                    f"silently does nothing", mod))
    return findings


def _local_name_arg_reads(fn: ast.AST, ns: Set[str]) -> Dict[str, Set[str]]:
    """For each local name, the args-attributes its assignments read —
    one level: ``d, m = parse_mesh_shape(args.mesh_shape)`` makes both
    ``d`` and ``m`` carry ``mesh_shape``."""
    out: Dict[str, Set[str]] = {}
    for sub in ast.walk(fn):
        if not isinstance(sub, ast.Assign):
            continue
        reads = _arg_attr_reads(sub.value, ns)
        if not reads:
            continue
        for t in sub.targets:
            targets = t.elts if isinstance(t, (ast.Tuple, ast.List)) \
                else [t]
            for x in targets:
                if isinstance(x, ast.Name):
                    out.setdefault(x.id, set()).update(reads)
    return out


# --------------------------------------------------------------------------
# GL023 — telemetry span names vs the trace validator's pins
# --------------------------------------------------------------------------

_TRACE_PINS_NAME = "TRACE_VALIDATED_NAMES"
_EMIT_ATTRS = {"begin", "end", "instant", "complete", "span", "phase",
               "name_track"}


def _emitted_names(idx: ProjectIndex) -> Set[str]:
    names: Set[str] = set()
    for mod in idx.modules.values():
        for sub in ast.walk(mod.tree):
            if isinstance(sub, ast.Call) \
                    and isinstance(sub.func, ast.Attribute) \
                    and sub.func.attr in _EMIT_ATTRS:
                for a in sub.args:
                    s = _resolve_str(mod, idx, a)
                    if s is not None:
                        names.add(s)
            elif isinstance(sub, ast.Dict) and sub.keys:
                keys = {_const_str(k) for k in sub.keys if k is not None}
                if "ph" in keys and "name" in keys:
                    for k, v in zip(sub.keys, sub.values):
                        if _const_str(k) == "name":
                            s = _resolve_str(mod, idx, v)
                            if s is not None:
                                names.add(s)
    return names


def check_telemetry_span_contract(idx: ProjectIndex) -> List[Finding]:
    pins_mod = pins_node = None
    pins: List[str] = []
    for mod in idx.modules.values():
        g = mod.globals.get(_TRACE_PINS_NAME)
        if g is not None and isinstance(g, (ast.Tuple, ast.List)):
            pins_mod, pins_node = mod, g
            pins = [s for s in (_resolve_str(mod, idx, e) for e in g.elts)
                    if s is not None]
            break
    if pins_mod is None:
        return []
    emitted = _emitted_names(idx)
    if not emitted:
        # no emission site in scope at all (e.g. a one-file lint of the
        # validator itself) — absence proves nothing, stay silent
        return []
    findings: List[Finding] = []
    for p in pins:
        if p not in emitted:
            findings.append(_finding(
                "GL023", pins_node,
                f"the trace validator pins event name {p!r} "
                f"({_TRACE_PINS_NAME}) but no telemetry call in the "
                f"project emits it — check_trace would reject every "
                f"trace, or the validation is dead", pins_mod))
    return findings
