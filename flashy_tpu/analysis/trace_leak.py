# FT001 — the dominant XLA failure mode (PAPERS.md, the pjit/TPUv4
# line): Python control flow and host conversions leaking into traced
# code. A stray int()/.item() inside a jitted function is not a style
# problem — it synchronously pulls a device value to the host (stalling
# the dispatch pipeline) or, worse, turns a traced value into a Python
# scalar that retriggers compilation per distinct value. This checker
# finds functions *reachable from* jit/wrap/shard_map entry points by a
# conservative intra-module reachability walk (call edges plus bare
# name references, so lax.scan bodies count) and flags host-boundary
# crossings inside them.
"""FT001 trace-leak: host syncs and Python branches inside traced code."""
import ast
import typing as tp

from .core import Checker, Finding, ProjectIndex, SourceFile, attr_chain

__all__ = ["TraceLeakChecker"]

# Callables whose function-valued arguments become traced entry points.
_ENTRY_BARE = {"jit", "pjit", "wrap", "shard_map"}
_ENTRY_CHAINS = {
    ("jax", "jit"), ("jax", "pjit"), ("jax", "shard_map"),
}
# Host-converting builtins: poison only when fed a traced-looking value.
_HOST_BUILTINS = {"int", "float", "bool", "complex"}
# Methods that ALWAYS materialize on the host.
_HOST_METHODS = {"item", "tolist"}
# numpy module aliases: np.asarray(device_value) is a hidden device->host
# round trip inside traced code.
_NUMPY_NAMES = {"np", "numpy", "onp"}
_JNP_NAMES = {"jnp", "jax"}

# Files whose non-traced host loops are still latency-critical: a
# .block_until_ready() there serializes the serve/decode pipeline.
def _is_hot_path(rel: str) -> bool:
    return "serve" in rel.split("/")[:-1] or rel.endswith("models/decoding.py")


def _is_entry_callee(func: ast.AST) -> bool:
    chain = attr_chain(func)
    if chain is None:
        return False
    if len(chain) == 1:
        return chain[0] in _ENTRY_BARE
    # only trusted module paths: `self.tracer.wrap(...)` must NOT count
    return chain in _ENTRY_CHAINS or chain[-2:] in _ENTRY_CHAINS


def _decorator_is_entry(node: ast.expr) -> bool:
    if _is_entry_callee(node):
        return True
    if isinstance(node, ast.Call):
        if _is_entry_callee(node.func):
            return True
        # @partial(jax.jit, ...) / @functools.partial(jax.jit, ...)
        chain = attr_chain(node.func)
        if chain and chain[-1] == "partial" and node.args:
            return _is_entry_callee(node.args[0])
    return False


class _FunctionInfo:
    def __init__(self, node: ast.AST, params: tp.Set[str],
                 parents: tp.Tuple[ast.AST, ...]) -> None:
        self.node = node
        self.params = params            # OWN parameters only
        self.parents = parents          # enclosing function defs, outer->inner
        self.refs: tp.Set[str] = set()  # every bare Name read in the body

    @property
    def name(self) -> str:
        return self.node.name  # type: ignore[attr-defined]


class _Module:
    """Scope-aware function table: a bare name reference is resolved to
    the NEAREST definition — functions nested inside the referencing
    function win over same-named module/class-level ones. Without this,
    a host-side method `prefill` would inherit traced-ness from the
    nested `prefill` that `_build_prefill` hands to jax.jit."""

    def __init__(self, tree: ast.Module) -> None:
        self.by_name: tp.Dict[str, tp.List[_FunctionInfo]] = {}
        self.info_of: tp.Dict[ast.AST, _FunctionInfo] = {}

        def visit(node: ast.AST, parents: tp.Tuple[ast.AST, ...]) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    args = child.args
                    params = {a.arg for a in (
                        list(args.posonlyargs) + list(args.args)
                        + list(args.kwonlyargs))}
                    if args.vararg:
                        params.add(args.vararg.arg)
                    if args.kwarg:
                        params.add(args.kwarg.arg)
                    info = _FunctionInfo(child, params, parents)
                    for sub in ast.walk(child):
                        if isinstance(sub, ast.Name):
                            info.refs.add(sub.id)
                    self.by_name.setdefault(child.name, []).append(info)
                    self.info_of[child] = info
                    visit(child, parents + (child,))
                else:
                    visit(child, parents)

        visit(tree, ())

    def traced_params(self, info: _FunctionInfo,
                      traced: tp.Set[_FunctionInfo]) -> tp.Set[str]:
        """Names holding traced values inside `info`: its own parameters
        plus those of enclosing functions that are THEMSELVES traced. A
        non-traced builder's parameters (capacity factors, flags) are
        trace-time constants for the closure — int() on them is fine."""
        params = set(info.params)
        for parent in info.parents:
            parent_info = self.info_of.get(parent)
            if parent_info is not None and parent_info in traced:
                params |= parent_info.params
        return params

    def resolve(self, name: str, site: tp.Optional[_FunctionInfo],
                ) -> tp.List[_FunctionInfo]:
        candidates = self.by_name.get(name, [])
        if not candidates:
            return []
        if site is not None:
            nested = [c for c in candidates if site.node in c.parents]
            if nested:
                return nested
        top = [c for c in candidates if not c.parents]
        return top or candidates


def _traced_roots(tree: ast.Module, module: _Module,
                  ) -> tp.Set[_FunctionInfo]:
    roots: tp.Set[_FunctionInfo] = set()
    for infos in module.by_name.values():
        for info in infos:
            decorators = getattr(info.node, "decorator_list", [])
            if any(_decorator_is_entry(d) for d in decorators):
                roots.add(info)

    # entry-point CALLS: jax.jit(f) / wrap(f) / shard_map(f, ...) —
    # resolve f in the scope of the function containing the call.
    def scan(node: ast.AST, site: tp.Optional[_FunctionInfo]) -> None:
        for child in ast.iter_child_nodes(node):
            child_site = site
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                matches = [i for i in module.by_name.get(child.name, [])
                           if i.node is child]
                child_site = matches[0] if matches else site
            if isinstance(child, ast.Call) and _is_entry_callee(child.func):
                for arg in (list(child.args)
                            + [kw.value for kw in child.keywords]):
                    if isinstance(arg, ast.Name):
                        roots.update(module.resolve(arg.id, child_site))
            scan(child, child_site)

    scan(tree, None)
    return roots


def _reachable(module: _Module,
               roots: tp.Set[_FunctionInfo]) -> tp.Set[_FunctionInfo]:
    seen = set(roots)
    frontier = list(roots)
    while frontier:
        info = frontier.pop()
        for ref in info.refs:
            for target in module.resolve(ref, info):
                if target not in seen:
                    seen.add(target)
                    frontier.append(target)
    return seen


def _mentions_any(node: ast.AST, names: tp.Set[str]) -> bool:
    return any(isinstance(sub, ast.Name) and sub.id in names
               for sub in ast.walk(node))


def _contains_jnp_call(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            chain = attr_chain(sub.func)
            if chain and len(chain) >= 2 and chain[0] in _JNP_NAMES:
                if chain[:2] == ("jax", "numpy") or chain[0] == "jnp":
                    return True
                if chain[:2] == ("jax", "lax"):
                    return True
    return False


def _own_body(node: ast.AST) -> tp.Iterator[ast.AST]:
    """Walk a function body WITHOUT descending into nested defs (those
    are separate reachability entries)."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        child = stack.pop()
        yield child
        if not isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
            stack.extend(ast.iter_child_nodes(child))


class TraceLeakChecker(Checker):
    code = "FT001"
    name = "trace-leak"
    explain = ("host conversions (int/float/bool/.item()/.tolist()/"
               "np.asarray/.block_until_ready) and Python branches on "
               "traced values inside functions reachable from "
               "jax.jit/wrap/shard_map, plus host syncs in serve/decode "
               "hot paths")

    def check(self, file: SourceFile,
              index: ProjectIndex) -> tp.Iterable[Finding]:
        if file.tree is None:
            return
        module = _Module(file.tree)
        roots = _traced_roots(file.tree, module)
        traced = _reachable(module, roots)
        flagged: tp.Set[tp.Tuple[int, int]] = set()

        def finding(node: ast.AST, message: str, hint: str) -> Finding:
            loc = (node.lineno, node.col_offset)  # type: ignore[attr-defined]
            flagged.add(loc)
            return Finding(self.code, file.rel, loc[0], loc[1], message, hint)

        for info in traced:
            params = module.traced_params(info, traced)
            yield from self._check_traced(file, info.name, info, params,
                                          finding)

        if _is_hot_path(file.rel):
            yield from self._check_hot_path(file, module, flagged, finding)

    def _check_traced(self, file: SourceFile, name: str, info: _FunctionInfo,
                      params: tp.Set[str],
                      finding: tp.Callable[..., Finding],
                      ) -> tp.Iterator[Finding]:
        for node in _own_body(info.node):
            if isinstance(node, ast.Call):
                chain = attr_chain(node.func)
                callee = chain[-1] if chain else ""
                # method name even when the receiver is a call result
                # (`batch.sum().item()` has no resolvable name chain)
                method = (node.func.attr
                          if isinstance(node.func, ast.Attribute) else "")
                if (callee in _HOST_BUILTINS and chain and len(chain) == 1
                        and node.args
                        and (_mentions_any(node.args[0], params)
                             or _contains_jnp_call(node.args[0]))):
                    yield finding(
                        node,
                        f"{callee}() on a traced value inside jitted "
                        f"function {name!r} forces a host sync / retrace",
                        "keep it on device (jnp ops) or hoist the scalar "
                        "out of the traced function")
                elif method in _HOST_METHODS:
                    yield finding(
                        node,
                        f".{method}() inside jitted function {name!r} "
                        "materializes a device value on the host",
                        "return the array and convert outside the jit "
                        "boundary")
                elif (callee == "asarray" and chain and len(chain) >= 2
                        and chain[-2] in _NUMPY_NAMES):
                    yield finding(
                        node,
                        f"np.asarray inside jitted function {name!r} "
                        "round-trips a device value through the host",
                        "use jnp.asarray (stays on device) or move the "
                        "conversion outside the traced function")
                elif method == "block_until_ready" or callee == "block_until_ready":
                    yield finding(
                        node,
                        f".block_until_ready() inside jitted function "
                        f"{name!r} is a no-op on tracers and a sync "
                        "everywhere else",
                        "remove it; sync outside the jit boundary")
            elif isinstance(node, (ast.If, ast.While)):
                if _contains_jnp_call(node.test):
                    yield finding(
                        node,
                        f"Python branch on a traced expression inside "
                        f"jitted function {name!r} (concretization error "
                        "or silent host sync)",
                        "use jnp.where / jax.lax.cond / jax.lax.select")

    def _check_hot_path(self, file: SourceFile, module: _Module,
                        flagged: tp.Set[tp.Tuple[int, int]],
                        finding: tp.Callable[..., Finding],
                        ) -> tp.Iterator[Finding]:
        # warm-up helpers legitimately sync (they pay compile+sync once,
        # off the steady-state path); everything else in serve/decode is
        # a per-step stall.
        warm_lines: tp.Set[int] = set()
        for infos in module.by_name.values():
            for info in infos:
                node = info.node
                if "warm" in info.name.lower():
                    warm_lines.update(
                        range(node.lineno,              # type: ignore[attr-defined]
                              (node.end_lineno or node.lineno) + 1))  # type: ignore[attr-defined]
        for node in ast.walk(file.tree):  # type: ignore[arg-type]
            if not isinstance(node, ast.Call):
                continue
            name = (node.func.attr if isinstance(node.func, ast.Attribute)
                    else node.func.id if isinstance(node.func, ast.Name)
                    else "")
            if name != "block_until_ready":
                continue
            loc = (node.lineno, node.col_offset)
            if loc in flagged or node.lineno in warm_lines:
                continue
            yield finding(
                node,
                "host sync (.block_until_ready) in a serve/decode hot "
                "path stalls the dispatch pipeline every step",
                "restrict device syncs to warmup()/metrics boundaries")
