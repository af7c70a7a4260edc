"""Per-module AST rules.

Each rule is a callable ``rule(mod: ModuleInfo) -> list[Finding]``. Rules are
deliberately repo-specific: every one is grounded in a bug this repo has
actually shipped (and a PR fixed by hand) or an invariant its tests pin —
see the rule catalog in the README for the id -> motivation table.

Directory scopes: the determinism rules police the deterministic planes
(``sim/``, ``core/``, ``runtime/``, ``launch/``); the Pallas rules police
``kernels/``; jit-hygiene and dtype rules run tree-wide.
"""
from __future__ import annotations

import ast
import re
from typing import Iterable, Optional

from .engine import Finding, ModuleInfo

__all__ = ["MODULE_RULES", "RULE_CATALOG"]

# directories (under src/repro/) whose behavior must be a pure function of
# explicit seeds and injected clocks
_DETERMINISTIC_DIRS = ("sim", "core", "runtime", "launch")
_KERNEL_DIR = "src/repro/kernels/"

_WALL_CLOCK = {
    "time.time", "time.time_ns", "time.monotonic", "time.monotonic_ns",
    "time.perf_counter", "time.perf_counter_ns",
    "datetime.datetime.now", "datetime.datetime.utcnow",
}
_RNG_ALLOWED = {"numpy.random.default_rng", "numpy.random.Generator",
                "numpy.random.SeedSequence", "numpy.random.BitGenerator",
                "numpy.random.Philox", "numpy.random.PCG64"}
_BACKEND_STATE = {
    "jax.default_backend", "jax.devices", "jax.local_devices",
    "jax.device_count", "jax.local_device_count", "jax.config",
    "jax.default_device",
}
_TRACE_PRIMS = {"jax.lax.scan", "jax.lax.while_loop", "jax.lax.fori_loop",
                "jax.lax.cond", "jax.lax.map", "jax.lax.switch"}
_SUB_FP32 = {"int8", "int16", "uint8", "bfloat16", "float16",
             "float8_e4m3fn", "float8_e5m2"}
_JIT_DOC_RE = re.compile(r"jitted|jax\.jit|lax\.scan")
_ROUND_NODE_RE = re.compile(r"round|node", re.IGNORECASE)


# ---------------------------------------------------------------------------
# Shared AST helpers
# ---------------------------------------------------------------------------

def _collect_aliases(tree: ast.Module) -> dict[str, str]:
    """Local name -> canonical dotted module path (``np`` -> ``numpy``,
    ``pl`` -> ``jax.experimental.pallas``, ``partial`` ->
    ``functools.partial``). Relative imports keep their bare module name —
    they never collide with the external libraries the rules match on."""
    aliases: dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.asname:
                    aliases[a.asname] = a.name
                else:
                    head = a.name.split(".")[0]
                    aliases[head] = head
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                aliases[a.asname or a.name] = f"{node.module}.{a.name}"
    return aliases


def _canonical(node: ast.AST, aliases: dict[str, str]) -> Optional[str]:
    """Canonical dotted name of a Name/Attribute chain, alias-resolved."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(aliases.get(node.id, node.id))
        return ".".join(reversed(parts))
    return None


def _scopes(tree: ast.Module) -> dict[int, str]:
    """id(node) -> dotted enclosing-scope name. A def/class node's own scope
    includes itself, so findings on a decorator read as that function's."""
    out: dict[int, str] = {}

    def visit(node: ast.AST, stack: list[str]) -> None:
        for child in ast.iter_child_nodes(node):
            s = stack
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                s = stack + [child.name]
            out[id(child)] = ".".join(s)
            visit(child, s)

    visit(tree, [])
    return out


class _Ctx:
    def __init__(self, mod: ModuleInfo):
        self.mod = mod
        self.aliases = _collect_aliases(mod.tree)
        self.scopes = _scopes(mod.tree)

    def canon(self, node: ast.AST) -> Optional[str]:
        return _canonical(node, self.aliases)

    def finding(self, rule: str, node: ast.AST, message: str) -> Finding:
        return Finding(rule=rule, path=self.mod.rel,
                       line=getattr(node, "lineno", 1), message=message,
                       scope=self.scopes.get(id(node), ""))


def _in_deterministic_scope(mod: ModuleInfo) -> bool:
    return any(mod.rel.startswith(f"src/repro/{d}/")
               for d in _DETERMINISTIC_DIRS)


def _walk_calls(tree: ast.Module) -> Iterable[ast.Call]:
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            yield node


# ---------------------------------------------------------------------------
# DET001 — wall-clock reads in deterministic planes
# ---------------------------------------------------------------------------

def rule_det001_wall_clock(mod: ModuleInfo) -> list[Finding]:
    """No ``time.time()`` (or any wall/monotonic-clock read) inside the
    deterministic planes: identical runs must produce identical event logs,
    so timing flows through an injectable ``clock`` callable (the pattern
    ``runtime/fault.py`` adopted after PR 7's nondeterministic fault logs).
    Referencing ``time.perf_counter`` as an injectable *default* is fine —
    only direct calls are flagged."""
    if not _in_deterministic_scope(mod):
        return []
    ctx = _Ctx(mod)
    out = []
    for call in _walk_calls(mod.tree):
        name = ctx.canon(call.func)
        if name in _WALL_CLOCK:
            out.append(ctx.finding(
                "DET001", call,
                f"wall-clock read `{name}()` in a deterministic plane - "
                "inject a clock callable instead (see runtime/fault.py)"))
    return out


# ---------------------------------------------------------------------------
# DET002 — process-global RNG
# ---------------------------------------------------------------------------

def rule_det002_global_rng(mod: ModuleInfo) -> list[Finding]:
    """No process-global RNG in the deterministic planes: ``np.random.seed``
    / ``np.random.<draw>`` and stdlib ``random.*`` share hidden state across
    call sites, so two features drawing from them perturb each other's
    streams. Use ``np.random.default_rng(...)`` generators (jax.random is
    keyed and always fine)."""
    if not _in_deterministic_scope(mod):
        return []
    ctx = _Ctx(mod)
    out = []
    for call in _walk_calls(mod.tree):
        name = ctx.canon(call.func)
        if not name:
            continue
        if name.startswith("numpy.random.") and name not in _RNG_ALLOWED:
            out.append(ctx.finding(
                "DET002", call,
                f"process-global numpy RNG `{name}` - construct a local "
                "np.random.default_rng generator instead"))
        elif name.startswith("random.") and name.count(".") == 1:
            out.append(ctx.finding(
                "DET002", call,
                f"stdlib global RNG `{name}` - use a seeded "
                "np.random.default_rng generator instead"))
    return out


# ---------------------------------------------------------------------------
# DET003 — domain-separated rng seeds
# ---------------------------------------------------------------------------

def rule_det003_rng_domain(mod: ModuleInfo) -> list[Finding]:
    """Every ``np.random.default_rng`` call in the deterministic planes must
    pass a tuple seed with a domain tag — ``(seed, 0xFA17)`` style (the
    ``sim/faults.py`` idiom). A bare ``default_rng(seed)`` makes two features
    seeded from the same scalar share one stream, so adding a draw to one
    silently reshuffles the other; no argument at all means OS entropy."""
    if not _in_deterministic_scope(mod):
        return []
    ctx = _Ctx(mod)
    out = []
    for call in _walk_calls(mod.tree):
        if ctx.canon(call.func) != "numpy.random.default_rng":
            continue
        if not call.args and not call.keywords:
            out.append(ctx.finding(
                "DET003", call,
                "unseeded np.random.default_rng() draws OS entropy - pass a "
                "domain-tagged tuple seed like (seed, 0xFA17)"))
            continue
        arg = call.args[0] if call.args else call.keywords[0].value
        if not (isinstance(arg, ast.Tuple) and len(arg.elts) >= 2):
            out.append(ctx.finding(
                "DET003", call,
                "np.random.default_rng seeded without a domain tag - pass a "
                "tuple seed like (seed, 0xFA17) so streams are "
                "domain-separated"))
    return out


# ---------------------------------------------------------------------------
# JIT001 — functools caches over stateful functions
# ---------------------------------------------------------------------------

def _cache_decorators(fn: ast.FunctionDef, ctx: _Ctx) -> list[ast.AST]:
    out = []
    for dec in fn.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        if ctx.canon(target) in ("functools.cache", "functools.lru_cache"):
            out.append(dec)
    return out


def _module_mutable_globals(tree: ast.Module) -> set[str]:
    """Module-level names bound to mutable containers (registries)."""
    mutable: set[str] = set()
    for node in tree.body:
        targets: list[ast.expr] = []
        if isinstance(node, ast.Assign):
            targets = node.targets
            value = node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets = [node.target]
            value = node.value
        else:
            continue
        is_mutable = isinstance(value, (ast.Dict, ast.List, ast.Set)) or (
            isinstance(value, ast.Call) and isinstance(value.func, ast.Name)
            and value.func.id in ("dict", "list", "set"))
        if is_mutable:
            for t in targets:
                if isinstance(t, ast.Name):
                    mutable.add(t.id)
    return mutable


def rule_jit001_cached_state(mod: ModuleInfo) -> list[Finding]:
    """``functools.cache``/``lru_cache`` must not memoize functions that
    read backend or module-global mutable state: the cache freezes the first
    answer for the life of the process (PR 5's bug — a cached
    ``_default_interpret`` pinned the Pallas backend choice made before a
    TPU was attached). Resolve live state per call, outside any cache."""
    ctx = _Ctx(mod)
    mutable_globals = _module_mutable_globals(mod.tree)
    out = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.FunctionDef):
            continue
        decs = _cache_decorators(node, ctx)
        if not decs:
            continue
        reasons = []
        local_names = {a.arg for a in node.args.args
                       + node.args.posonlyargs + node.args.kwonlyargs}
        for inner in ast.walk(node):
            name = ctx.canon(inner) if isinstance(
                inner, (ast.Attribute, ast.Name)) else None
            if name in _BACKEND_STATE:
                reasons.append(f"reads live backend state `{name}`")
            elif isinstance(inner, ast.Global):
                reasons.append("declares `global` names")
            elif (isinstance(inner, ast.Name) and isinstance(inner.ctx,
                                                             ast.Load)
                  and inner.id in mutable_globals
                  and inner.id not in local_names):
                reasons.append(
                    f"reads module-global mutable `{inner.id}`")
        if reasons:
            uniq = sorted(set(reasons))
            out.append(ctx.finding(
                "JIT001", decs[0],
                f"functools cache on `{node.name}` which {'; '.join(uniq)} - "
                "the cache freezes the first answer for the process "
                "lifetime; resolve per call instead"))
    return out


# ---------------------------------------------------------------------------
# JIT002 — host syncs inside traced code
# ---------------------------------------------------------------------------

def _traced_functions(mod: ModuleInfo, ctx: _Ctx) -> dict[int, str]:
    """id(FunctionDef/Lambda) -> why it's traced. Covers @jax.jit (direct,
    @jit, and functools.partial(jax.jit, ...)), bodies handed to lax control
    flow (scan/while/fori/cond/map/switch), and defs nested inside either."""
    by_name: dict[str, ast.FunctionDef] = {}
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.FunctionDef):
            by_name.setdefault(node.name, node)

    traced: dict[int, str] = {}

    def mark(fn: ast.AST, why: str) -> None:
        if id(fn) in traced:
            return
        traced[id(fn)] = why
        for inner in ast.walk(fn):
            if inner is not fn and isinstance(inner, (ast.FunctionDef,
                                                      ast.Lambda)):
                traced.setdefault(id(inner), why)

    for node in ast.walk(mod.tree):
        if isinstance(node, ast.FunctionDef):
            for dec in node.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                name = ctx.canon(target)
                if name == "jax.jit":
                    mark(node, "@jax.jit")
                elif (name == "functools.partial" and isinstance(dec, ast.Call)
                      and dec.args and ctx.canon(dec.args[0]) == "jax.jit"):
                    mark(node, "@partial(jax.jit, ...)")
        elif isinstance(node, ast.Call):
            prim = ctx.canon(node.func)
            if prim in _TRACE_PRIMS:
                for arg in node.args:
                    if isinstance(arg, ast.Lambda):
                        mark(arg, f"body of {prim}")
                    elif isinstance(arg, ast.Name) and arg.id in by_name:
                        mark(by_name[arg.id], f"body of {prim}")
    return traced


def rule_jit002_host_sync(mod: ModuleInfo) -> list[Finding]:
    """No host syncs on traced values: ``.item()`` / ``float()`` / ``int()``
    / ``np.asarray()`` inside a ``@jax.jit`` function or a ``lax`` control-
    flow body either crashes under tracing or silently forces a device
    round-trip per call. Shape arithmetic (``int(x.shape[0])`` etc.) is
    static and exempt."""
    ctx = _Ctx(mod)
    traced = _traced_functions(mod, ctx)
    if not traced:
        return []
    out = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.FunctionDef, ast.Lambda)):
            continue
        why = traced.get(id(node))
        if why is None:
            continue
        body = node.body if isinstance(node.body, list) else [node.body]
        for stmt in body:
            for inner in ast.walk(stmt):
                if not isinstance(inner, ast.Call):
                    continue
                if (isinstance(inner.func, ast.Attribute)
                        and inner.func.attr == "item" and not inner.args):
                    out.append(ctx.finding(
                        "JIT002", inner,
                        f"`.item()` host sync inside traced code ({why})"))
                    continue
                name = ctx.canon(inner.func)
                if name in ("numpy.asarray", "numpy.array"):
                    out.append(ctx.finding(
                        "JIT002", inner,
                        f"`{name}` materializes a traced value on the host "
                        f"inside traced code ({why}) - use jnp instead"))
                elif (isinstance(inner.func, ast.Name)
                      and inner.func.id in ("float", "int")
                      and len(inner.args) == 1
                      and not isinstance(inner.args[0], ast.Constant)):
                    seg = ast.get_source_segment(mod.source, inner) or ""
                    if not re.search(r"shape|ndim|len\(|size", seg):
                        out.append(ctx.finding(
                            "JIT002", inner,
                            f"`{inner.func.id}(...)` forces a concrete value "
                            f"inside traced code ({why}) - keep it an array "
                            "or hoist to a static argument"))
    # dedupe: nested defs are walked once from each enclosing traced def
    seen: set[tuple] = set()
    uniq = []
    for f in out:
        key = (f.rule, f.path, f.line, f.message)
        if key not in seen:
            seen.add(key)
            uniq.append(f)
    return uniq


# ---------------------------------------------------------------------------
# JIT003 — Python round/node loops in modules advertising jitted paths
# ---------------------------------------------------------------------------

def rule_jit003_python_loops(mod: ModuleInfo) -> list[Finding]:
    """Modules whose docstring advertises a jitted path must not grow Python
    loops over rounds/nodes: per-round Python dispatch is exactly the host
    overhead the batched plane exists to remove (ROADMAP: move the remaining
    round loop into the jitted plane). Retained ``*_reference`` / driver /
    precompute functions are host-side by contract and exempt."""
    if not _JIT_DOC_RE.search(mod.docstring):
        return []
    ctx = _Ctx(mod)
    out = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.For):
            continue
        scope = ctx.scopes.get(id(node), "")
        leaf = scope.rsplit(".", 1)[-1] if scope else ""
        if (leaf.endswith("_reference") or "driver" in leaf
                or "precompute" in leaf or "host" in leaf):
            continue
        text = " ".join(
            ast.get_source_segment(mod.source, part) or ""
            for part in (node.target, node.iter))
        if _ROUND_NODE_RE.search(text):
            out.append(ctx.finding(
                "JIT003", node,
                "Python loop over rounds/nodes in a module advertising "
                "jitted paths - fold into lax.scan/vmap or move to a "
                "*_reference/driver function"))
    return out


# ---------------------------------------------------------------------------
# DTYPE001 — float64 flowing into jax arrays
# ---------------------------------------------------------------------------

def _is_float64(node: ast.AST, ctx: _Ctx) -> bool:
    if isinstance(node, ast.Constant) and node.value == "float64":
        return True
    return ctx.canon(node) in ("numpy.float64", "jax.numpy.float64")


def rule_dtype001_float64_into_jax(mod: ModuleInfo) -> list[Finding]:
    """No float64 flowing into jax arrays: jax runs x64-disabled, so an
    explicit float64 dtype on a ``jnp.*`` constructor (or an
    ``astype(jnp.float64)``) either silently truncates to f32 or — with x64
    enabled on one machine and not another — forks numerics between hosts.
    Host-side ``np.float64`` is the contract and stays untouched."""
    ctx = _Ctx(mod)
    out = []
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Attribute) and \
                ctx.canon(node) == "jax.numpy.float64":
            out.append(ctx.finding(
                "DTYPE001", node,
                "`jnp.float64` used - jax arrays are f32 by policy here; "
                "keep float64 on the numpy host plane"))
        if not isinstance(node, ast.Call):
            continue
        name = ctx.canon(node.func)
        if not name or not name.startswith("jax.numpy."):
            continue
        dtype_args = [kw.value for kw in node.keywords if kw.arg == "dtype"]
        dtype_args += list(node.args[1:3])   # dtype is positional arg 1-2
        for arg in dtype_args:
            if isinstance(arg, ast.Constant) and arg.value == "float64" or \
                    ctx.canon(arg) == "numpy.float64":
                out.append(ctx.finding(
                    "DTYPE001", node,
                    f"float64 dtype passed into `{name}` - jax arrays stay "
                    "f32; convert on the numpy host plane instead"))
    return out


# ---------------------------------------------------------------------------
# DTYPE002 — jax eigensolves outside an enable_x64 scope
# ---------------------------------------------------------------------------

_JAX_EIG = {"jax.numpy.linalg.eig", "jax.numpy.linalg.eigvals",
            "jax.numpy.linalg.eigh", "jax.numpy.linalg.eigvalsh"}


def rule_dtype002_eig_needs_x64(mod: ModuleInfo) -> list[Finding]:
    """Jax eigensolves must sit lexically inside a ``with
    jax.enable_x64(True):`` block: jax defaults to f32, so
    ``jnp.linalg.eig*`` on a float64 capacity/W matrix silently downgrades
    and the paper's lambda loses ~4 digits against the numpy plane (the
    ``rate_opt`` ``backend="jax"`` bug). The scope must be lexical — tracing
    under it is what keeps the compiled eig in float64."""
    ctx = _Ctx(mod)
    covered: set[int] = set()
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.With):
            continue
        if any(isinstance(item.context_expr, ast.Call)
               and ctx.canon(item.context_expr.func)
               == "jax.enable_x64"
               for item in node.items):
            covered.update(id(n) for n in ast.walk(node))
    out = []
    for node in _walk_calls(mod.tree):
        name = ctx.canon(node.func)
        if name in _JAX_EIG and id(node) not in covered:
            out.append(ctx.finding(
                "DTYPE002", node,
                f"`{name[10:]}` outside an `enable_x64()` scope - jax "
                "eigensolves run f32 by default and silently downgrade the "
                "spectral lambda; wrap the traced region in "
                "`with jax.enable_x64(True):`"))
    return out


# ---------------------------------------------------------------------------
# PAL001 / PAL002 — Pallas kernel lint
# ---------------------------------------------------------------------------

def _is_pallas_call(node: ast.Call, ctx: _Ctx) -> bool:
    name = ctx.canon(node.func)
    return bool(name and name.endswith(".pallas_call")) or (
        isinstance(node.func, ast.Attribute)
        and node.func.attr == "pallas_call")


def rule_pal001_interpret_routing(mod: ModuleInfo) -> list[Finding]:
    """Kernel modules must route interpret-mode through
    ``_default_interpret`` (resolved per call, outside the jit cache):
    ``interpret`` defaults must be ``None`` — a literal ``True`` pins CPU
    CI behavior onto TPU deployments, a literal ``False`` breaks every
    non-TPU host, and a cached choice is PR 5's frozen-backend bug."""
    if not mod.rel.startswith(_KERNEL_DIR):
        return []
    ctx = _Ctx(mod)
    out = []
    has_pallas = False
    mentions_router = "_default_interpret" in mod.source
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call) and _is_pallas_call(node, ctx):
            has_pallas = True
            for kw in node.keywords:
                if kw.arg == "interpret" and isinstance(kw.value,
                                                        ast.Constant):
                    out.append(ctx.finding(
                        "PAL001", node,
                        "pallas_call with a literal `interpret` - thread the "
                        "caller's choice through and default via "
                        "_default_interpret()"))
        if isinstance(node, ast.FunctionDef):
            args = node.args
            all_args = args.posonlyargs + args.args
            defaults = args.defaults
            offset = len(all_args) - len(defaults)
            pairs = [(a, defaults[i - offset])
                     for i, a in enumerate(all_args) if i >= offset]
            pairs += [(a, d) for a, d in zip(args.kwonlyargs, args.kw_defaults)
                      if d is not None]
            for a, d in pairs:
                if a.arg == "interpret" and isinstance(d, ast.Constant) \
                        and isinstance(d.value, bool):
                    out.append(ctx.finding(
                        "PAL001", node,
                        f"`{node.name}` hardcodes interpret={d.value} - "
                        "default must be None and resolve via "
                        "_default_interpret() per call"))
    if has_pallas and not mentions_router:
        out.append(Finding(
            "PAL001", mod.rel, 1,
            "module calls pallas_call but never routes through "
            "_default_interpret - interpret-mode choice must track the live "
            "backend"))
    return out


def rule_pal002_fp32_accumulate(mod: ModuleInfo) -> list[Finding]:
    """Kernel bodies consuming sub-fp32 tiles must accumulate in fp32:
    low-precision intermediates (a bf16/int8 accumulator, or an
    ``astype(<sub-fp32>)`` feeding further arithmetic) lose exactly the
    mantissa bits the parity pins measure. Casting at the output store is
    the one legitimate down-cast."""
    if not mod.rel.startswith(_KERNEL_DIR):
        return []
    ctx = _Ctx(mod)
    kernels: list[ast.FunctionDef] = []
    by_name = {n.name: n for n in ast.walk(mod.tree)
               if isinstance(n, ast.FunctionDef)}
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call) and _is_pallas_call(node, ctx) \
                and node.args and isinstance(node.args[0], ast.Name):
            fn = by_name.get(node.args[0].id)
            if fn is not None and fn not in kernels:
                kernels.append(fn)

    def sub_fp32(arg: ast.AST) -> Optional[str]:
        name = ctx.canon(arg)
        if name:
            leaf = name.rsplit(".", 1)[-1]
            if leaf in _SUB_FP32:
                return leaf
        if isinstance(arg, ast.Constant) and arg.value in _SUB_FP32:
            return str(arg.value)
        return None

    out = []
    for fn in kernels:
        # the direct value of `o_ref[...] = expr` may down-cast (output store)
        store_values = {id(stmt.value) for stmt in ast.walk(fn)
                        if isinstance(stmt, ast.Assign)
                        and any(isinstance(t, ast.Subscript)
                                for t in stmt.targets)}
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = ctx.canon(node.func)
            if name in ("jax.numpy.zeros", "jax.numpy.ones",
                        "jax.numpy.empty", "jax.numpy.full"):
                dtypes = [kw.value for kw in node.keywords
                          if kw.arg == "dtype"] + list(node.args[1:3])
                for d in dtypes:
                    leaf = sub_fp32(d)
                    if leaf:
                        out.append(ctx.finding(
                            "PAL002", node,
                            f"kernel `{fn.name}` allocates a {leaf} "
                            "accumulator - accumulate in fp32, cast at the "
                            "output store"))
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr == "astype" and node.args
                  and id(node) not in store_values):
                leaf = sub_fp32(node.args[0])
                if leaf:
                    out.append(ctx.finding(
                        "PAL002", node,
                        f"kernel `{fn.name}` casts an intermediate to "
                        f"{leaf} - accumulate in fp32, cast only at the "
                        "output store"))
    return out


MODULE_RULES = [
    rule_det001_wall_clock,
    rule_det002_global_rng,
    rule_det003_rng_domain,
    rule_jit001_cached_state,
    rule_jit002_host_sync,
    rule_jit003_python_loops,
    rule_dtype001_float64_into_jax,
    rule_dtype002_eig_needs_x64,
    rule_pal001_interpret_routing,
    rule_pal002_fp32_accumulate,
]

RULE_CATALOG = {
    "DET001": "wall-clock read in a deterministic plane (inject a clock)",
    "DET002": "process-global RNG (np.random.* / stdlib random) in a "
              "deterministic plane",
    "DET003": "np.random.default_rng without a domain-tagged tuple seed",
    "JIT001": "functools.cache/lru_cache over backend or mutable "
              "module-global state",
    "JIT002": "host sync (.item()/float()/int()/np.asarray) inside traced "
              "code",
    "JIT003": "Python round/node loop in a module advertising jitted paths",
    "DTYPE001": "float64 flowing into jax arrays",
    "DTYPE002": "jnp.linalg.eig* outside a jax.enable_x64 "
                "scope",
    "PAL001": "Pallas interpret-mode not routed through _default_interpret",
    "PAL002": "sub-fp32 accumulation inside a Pallas kernel body",
    "PAR001": "public *_batch/solve_* symbol with no *_reference sibling",
    "PAR002": "batched/reference pair never pinned together by any test",
    "ENG001": "file does not parse",
}
