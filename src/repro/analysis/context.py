"""Per-file analysis context shared by the rule packs.

One :class:`FileContext` per linted Python file carries the parsed
tree plus lazily computed, cached analyses every jax-discipline rule
needs:

* **import resolution** — a map from local names to the dotted origin
  they were imported from (``jnp`` → ``jax.numpy``, relative imports
  resolved against the module's package), and :meth:`resolve` turning
  a ``Name``/``Attribute`` chain into a dotted path through that map;
* **function index** — every ``def``/``lambda`` with its parameters
  and statically-declared arguments;
* **traced reachability** — the set of functions reachable from a
  ``jit``/``shard_map``/``pallas_call``/``scan``-style trace site in
  the same module (decorated, passed as a function argument to a trace
  wrapper, or called from an already-traced function), which is what
  "inside a trace" means to JL001/JL005.

Everything is intra-module by design: a dependency-free ``ast`` pass
cannot see across imports, so reachability is conservative — it only
claims tracedness it can prove, and the fixture suite pins the
patterns it must catch.
"""

from __future__ import annotations

import ast
import pathlib
from functools import cached_property

__all__ = ["FileContext", "FunctionInfo", "TRACE_WRAPPERS"]

# dotted names (post import-resolution) that trace the function they
# are given; bare-name imports resolve to these through the import map
TRACE_WRAPPERS = frozenset({
    "jax.jit", "jax.pjit", "jax.experimental.pjit.pjit",
    "jax.vmap", "jax.pmap", "jax.checkpoint", "jax.remat",
    "jax.grad", "jax.value_and_grad", "jax.lax.scan", "jax.lax.map",
    "jax.lax.while_loop", "jax.lax.cond", "jax.lax.fori_loop",
    "jax.lax.switch", "jax.lax.associative_scan",
    "jax.shard_map",
    "jax.experimental.pallas.pallas_call",
})
# unambiguous last components: anything.pallas_call / anything.shard_map
# is a trace site no matter how the module was imported
_TRACE_SUFFIXES = frozenset({"pallas_call", "shard_map"})


class FunctionInfo:
    """Static facts about one function definition (or lambda)."""

    def __init__(self, node, qualname: str, parent):
        self.node = node
        self.qualname = qualname
        self.parent = parent          # enclosing FunctionInfo or None
        args = node.args
        self.params = [a.arg for a in
                       (args.posonlyargs + args.args + args.kwonlyargs)]
        if args.vararg:
            self.params.append(args.vararg.arg)
        if args.kwarg:
            self.params.append(args.kwarg.arg)
        self.static_params: set[str] = set()

    @property
    def name(self) -> str:
        """Bare function name (``<lambda>`` for lambdas)."""
        return getattr(self.node, "name", "<lambda>")


class FileContext:
    """Parsed file + cached shared analyses handed to every rule."""

    def __init__(self, path: pathlib.Path, rel: str, text: str):
        self.path = path
        self.rel = rel                  # root-relative posix path
        self.text = text
        self.lines = text.splitlines()
        self.tree = ast.parse(text, filename=str(path))
        self.module = self._module_name(rel)

    @staticmethod
    def _module_name(rel: str) -> str:
        parts = pathlib.PurePosixPath(rel).with_suffix("").parts
        if "src" in parts:
            parts = parts[parts.index("src") + 1:]
        if parts and parts[-1] == "__init__":
            parts = parts[:-1]
        return ".".join(parts)

    # ------------------------------------------------------------ imports
    @cached_property
    def imports(self) -> dict:
        """Local name -> dotted origin, for every import in the file."""
        out: dict[str, str] = {}
        pkg_parts = self.module.split(".")[:-1] if self.module else []
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.asname:
                        out[alias.asname] = alias.name
                    else:
                        head = alias.name.split(".")[0]
                        out[head] = head
            elif isinstance(node, ast.ImportFrom):
                if node.level:
                    base_parts = pkg_parts[:len(pkg_parts) - (node.level - 1)]
                    base = ".".join(base_parts)
                    if node.module:
                        base = f"{base}.{node.module}" if base else node.module
                else:
                    base = node.module or ""
                for alias in node.names:
                    if alias.name == "*":
                        continue
                    local = alias.asname or alias.name
                    out[local] = f"{base}.{alias.name}" if base else alias.name
        return out

    def resolve(self, node) -> str:
        """Dotted path of a Name/Attribute chain through the import map.

        Unresolvable roots keep their raw name (``key.item`` stays
        ``key.item``), so callers can still match on suffixes. Returns
        ``""`` for non-chain expressions.
        """
        parts = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return ""
        root = self.imports.get(node.id, node.id)
        parts.append(root)
        return ".".join(reversed(parts))

    def is_trace_wrapper(self, node) -> bool:
        """Whether an expression names a jit/shard_map/pallas_call-style
        tracer."""
        dotted = self.resolve(node)
        if not dotted:
            return False
        return (dotted in TRACE_WRAPPERS
                or dotted.rsplit(".", 1)[-1] in _TRACE_SUFFIXES)

    # ---------------------------------------------------------- functions
    @cached_property
    def functions(self) -> list:
        """Every function/lambda in the file as :class:`FunctionInfo`."""
        infos: list[FunctionInfo] = []

        def visit(node, qual, parent):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    info = FunctionInfo(child, f"{qual}{child.name}", parent)
                    info.static_params = _static_params(child, self)
                    infos.append(info)
                    visit(child, f"{qual}{child.name}.", info)
                elif isinstance(child, ast.Lambda):
                    info = FunctionInfo(child, f"{qual}<lambda>", parent)
                    infos.append(info)
                    visit(child, f"{qual}<lambda>.", info)
                elif isinstance(child, ast.ClassDef):
                    visit(child, f"{qual}{child.name}.", parent)
                else:
                    visit(child, qual, parent)

        visit(self.tree, "", None)
        return infos

    @cached_property
    def functions_by_name(self) -> dict:
        """Bare name -> list[FunctionInfo] (conservative, module-wide)."""
        out: dict[str, list] = {}
        for info in self.functions:
            out.setdefault(info.name, []).append(info)
        return out

    @cached_property
    def _info_by_node(self) -> dict:
        return {id(info.node): info for info in self.functions}

    # ------------------------------------------------------ tracedness
    @cached_property
    def traced_functions(self) -> list:
        """Functions reachable from a trace site, deepest contract first.

        Roots: decorated with a trace wrapper (directly or through
        ``functools.partial``), or passed by name/lambda to a trace
        wrapper call. Closure: a traced function tracing through a
        locally-defined callee marks the callee traced too.
        """
        traced: set[int] = set()

        for info in self.functions:
            for deco in getattr(info.node, "decorator_list", []):
                target = deco.func if isinstance(deco, ast.Call) else deco
                if self.is_trace_wrapper(target):
                    traced.add(id(info.node))
                elif (isinstance(deco, ast.Call)
                      and self.resolve(deco.func) in ("functools.partial",
                                                      "partial")
                      and deco.args
                      and self.is_trace_wrapper(deco.args[0])):
                    traced.add(id(info.node))

        for node in ast.walk(self.tree):
            if not (isinstance(node, ast.Call)
                    and self.is_trace_wrapper(node.func)):
                continue
            cands = list(node.args) + [kw.value for kw in node.keywords]
            for arg in cands:
                if isinstance(arg, ast.Lambda):
                    traced.add(id(arg))
                elif isinstance(arg, ast.Name):
                    for info in self.functions_by_name.get(arg.id, []):
                        traced.add(id(info.node))

        # closure over intra-module calls from traced bodies
        changed = True
        while changed:
            changed = False
            for info in self.functions:
                if id(info.node) not in traced:
                    continue
                for sub in self._own_body_walk(info.node):
                    if (isinstance(sub, ast.Call)
                            and isinstance(sub.func, ast.Name)):
                        for callee in self.functions_by_name.get(
                                sub.func.id, []):
                            if id(callee.node) not in traced:
                                traced.add(id(callee.node))
                                changed = True
        return [info for info in self.functions if id(info.node) in traced]

    @staticmethod
    def _own_body_walk(fn_node):
        """Walk a function body WITHOUT descending into nested defs
        (nested functions are analyzed separately if reachable)."""
        stack = list(ast.iter_child_nodes(fn_node))
        while stack:
            node = stack.pop()
            yield node
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                continue
            stack.extend(ast.iter_child_nodes(node))


def _static_params(fn_node, ctx: FileContext) -> set:
    """Parameter names declared static via jit decorator kwargs."""
    static: set[str] = set()
    args = fn_node.args
    positional = [a.arg for a in (args.posonlyargs + args.args)]
    for deco in fn_node.decorator_list:
        if not isinstance(deco, ast.Call):
            continue
        target = deco.func
        if isinstance(target, ast.Call):
            continue
        if not (ctx.is_trace_wrapper(target)
                or ctx.resolve(target) in ("functools.partial", "partial")):
            continue
        for kw in deco.keywords:
            if kw.arg == "static_argnames":
                for s in ast.walk(kw.value):
                    if isinstance(s, ast.Constant) and isinstance(s.value,
                                                                  str):
                        static.add(s.value)
            elif kw.arg == "static_argnums":
                for s in ast.walk(kw.value):
                    if isinstance(s, ast.Constant) and isinstance(s.value,
                                                                  int):
                        if 0 <= s.value < len(positional):
                            static.add(positional[s.value])
    return static
