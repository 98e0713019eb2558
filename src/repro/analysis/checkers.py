"""The repo-specific checkers. Importing this module registers them all
(same pattern as the engine's built-in substrates).

Taint model (shared by the host-sync and recompile checkers): inside one
function, a value is *traced/device* when it comes from a ``jnp.`` /
``jax.`` / ``lax.`` call (except ``jax.device_get`` — the explicit,
sanctioned way to cross back to the host), from calling a jit-wrapped
alias (``self._decode_fn`` and friends), or from a name such a value was
assigned / unpacked / iterated into. Function parameters are *not*
tainted — cross-function taint is intentionally out of scope, which
keeps the pass quiet enough to gate CI.
"""
from __future__ import annotations

import ast
from typing import Iterable, List, Optional, Set, Tuple

from repro.analysis import callgraph
from repro.analysis.callgraph import attr_chain
from repro.analysis.lint import (Checker, Finding, ModuleInfo, Project,
                                 register_checker)

# device-array attributes that are static python values, not arrays
_STATIC_ATTRS = {"shape", "dtype", "ndim", "size", "itemsize", "sharding",
                 "device"}
# builtins that never return device values regardless of their arguments
_HOST_BUILTINS = {"len", "range", "enumerate", "zip", "str", "repr",
                  "isinstance", "type", "id", "print", "sorted",
                  "reversed", "format", "hash"}
_SYNC_BUILTINS = ("float", "int", "bool", "complex")
_SYNC_METHODS = ("item", "tolist")
_DEVICE_ROOTS = ("jnp", "lax")
# jax.* members that return host-side objects (or are explicit syncs)
_JAX_HOST_MEMBERS = {"device_get", "devices", "local_devices",
                     "device_count", "local_device_count",
                     "default_backend", "process_index", "process_count"}
# AOT-inspection methods: host metadata, not device values
_AOT_METHODS = {"lower", "compile", "cost_analysis", "memory_analysis",
                "as_text", "as_hlo_text"}

LANE = 128
SUBLANE = 8


def _is_device_call(chain: List[str], full: str) -> bool:
    if chain[0] in _DEVICE_ROOTS:
        return True
    if full.split(".")[0] == "jax":
        rest = full.split(".")[1:]
        if rest and rest[0] in _JAX_HOST_MEMBERS:
            return False
        return True
    return False


class _FunctionTaint:
    """Per-function forward taint over locally-derived device values."""

    def __init__(self, fn: ast.AST, module: ModuleInfo, project: Project,
                 class_qual: Optional[str]):
        self.fn = fn
        self.module = module
        self.project = project
        self.imports = project.graph.imports.get(module.name, {})
        self.jit_attrs = project.graph.jit_self_aliases.get(
            class_qual or "", set())
        self.tainted: Set[str] = set()
        self._local_jit_names = self._find_local_jit_names()
        self._compute()

    # -- setup ----------------------------------------------------------
    def _full(self, chain: List[str]) -> str:
        head = self.imports.get(chain[0], chain[0])
        return ".".join([head] + chain[1:])

    def _find_local_jit_names(self) -> Set[str]:
        """Names bound to ``jax.jit(...)`` results inside this function
        (calls through them return device values)."""
        out: Set[str] = set()
        for node in self._stmts():
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                for call in callgraph._wrapped_calls(node.value):
                    chain = attr_chain(call.func)
                    if chain and self._full(chain).split(".")[-1] in \
                            callgraph._JIT_WRAPPERS:
                        out.add(node.targets[0].id)
        return out

    def _stmts(self) -> Iterable[ast.AST]:
        """All statements of this function, not descending into nested
        defs (separate functions) but descending into lambdas."""
        stack = list(ast.iter_child_nodes(self.fn))
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            yield node
            stack.extend(ast.iter_child_nodes(node))

    # -- taint ----------------------------------------------------------
    def is_tainted(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in _STATIC_ATTRS:
                return False
            return self.is_tainted(node.value)
        if isinstance(node, (ast.Subscript, ast.Starred)):
            return self.is_tainted(node.value)
        if isinstance(node, ast.Call):
            return self._call_tainted(node)
        if isinstance(node, ast.BinOp):
            return self.is_tainted(node.left) or self.is_tainted(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.is_tainted(node.operand)
        if isinstance(node, ast.BoolOp):
            return any(self.is_tainted(v) for v in node.values)
        if isinstance(node, ast.Compare):
            # identity / membership tests yield host bools (no sync)
            if all(isinstance(op, (ast.Is, ast.IsNot, ast.In, ast.NotIn))
                   for op in node.ops):
                return False
            return self.is_tainted(node.left) or any(
                self.is_tainted(c) for c in node.comparators)
        if isinstance(node, ast.IfExp):
            return self.is_tainted(node.body) or self.is_tainted(node.orelse)
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return any(self.is_tainted(e) for e in node.elts)
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp)):
            return (self.is_tainted(node.elt) or
                    any(self.is_tainted(g.iter) for g in node.generators))
        return False

    def _call_tainted(self, node: ast.Call) -> bool:
        chain = attr_chain(node.func)
        if chain is not None:
            if len(chain) == 1 and chain[0] in _HOST_BUILTINS:
                return False
            if len(chain) == 1 and chain[0] in _SYNC_BUILTINS:
                return False          # result is a host scalar
            full = self._full(chain)
            if full.split(".")[0] in ("np", "numpy", "math", "time", "os"):
                return False
            parts = full.split(".")
            if parts[0] == "jax" and len(parts) >= 2 and \
                    parts[1] in _JAX_HOST_MEMBERS:
                return False          # explicit device->host crossing
            if _is_device_call(chain, full):
                return True
            if chain[0] == "self" and len(chain) >= 2 \
                    and chain[1] in self.jit_attrs:
                return True
            if chain[0] in self._local_jit_names:
                return True
            if len(chain) >= 2 and chain[-1] in _SYNC_METHODS:
                return False          # .item()/.tolist() -> host
        # a method on a tainted receiver returns a device value
        # (tok.astype(...), jnp.argmax(x).astype(...), plan.apply(...))
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr not in _SYNC_METHODS and \
                node.func.attr not in _AOT_METHODS and \
                self.is_tainted(node.func.value):
            return True
        # unknown callable: propagate through arguments (min/max/sum of
        # device values stay device values)
        return any(self.is_tainted(a) for a in node.args)

    def _taint_target(self, target: ast.AST) -> None:
        if isinstance(target, ast.Name):
            self.tainted.add(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                self._taint_target(e)
        elif isinstance(target, ast.Starred):
            self._taint_target(target.value)

    def _compute(self) -> None:
        for _ in range(4):            # fixpoint over loop-carried taint
            before = len(self.tainted)
            for node in self._stmts():
                if isinstance(node, ast.Assign):
                    if self.is_tainted(node.value):
                        for t in node.targets:
                            self._taint_target(t)
                elif isinstance(node, ast.AnnAssign) and node.value:
                    if self.is_tainted(node.value):
                        self._taint_target(node.target)
                elif isinstance(node, ast.AugAssign):
                    if self.is_tainted(node.value):
                        self._taint_target(node.target)
                elif isinstance(node, ast.For):
                    if self.is_tainted(node.iter):
                        self._taint_target(node.target)
                elif isinstance(node, ast.NamedExpr):
                    if self.is_tainted(node.value):
                        self._taint_target(node.target)
                elif isinstance(node, ast.withitem):
                    if node.optional_vars is not None and \
                            self.is_tainted(node.context_expr):
                        self._taint_target(node.optional_vars)
                elif isinstance(node, ast.comprehension):
                    if self.is_tainted(node.iter):
                        self._taint_target(node.target)
                elif isinstance(node, ast.Expr) and \
                        isinstance(node.value, ast.Call):
                    # container.append(device_value) taints the container
                    call = node.value
                    chain = attr_chain(call.func)
                    if chain and len(chain) == 2 and chain[-1] in (
                            "append", "extend", "insert", "add") and \
                            any(self.is_tainted(a) for a in call.args):
                        self.tainted.add(chain[0])
            if len(self.tainted) == before:
                break


def _functions_of(module: ModuleInfo, project: Project
                  ) -> Iterable[Tuple[str, callgraph.FunctionInfo]]:
    for qual, info in project.graph.functions.items():
        if info.module == module.name:
            yield qual, info


# ---------------------------------------------------------------------------
class HostSyncChecker(Checker):
    """RPR101/RPR102: implicit device->host syncs on the hot path.

    ``jax.device_get`` is the sanctioned crossing: its result is a host
    array, so ``float(jax.device_get(x))`` is clean while ``float(x)``
    on a traced value flags.
    """

    name = "host-sync"
    rules = ("RPR101", "RPR102")

    def check(self, project: Project, module: ModuleInfo
              ) -> Iterable[Finding]:
        for qual, info in _functions_of(module, project):
            if not project.is_hot(qual):
                continue
            taint = _FunctionTaint(info.node, module, project,
                                   info.class_qual)
            yield from self._check_fn(project, module, qual, info, taint)

    def _check_fn(self, project, module, qual, info, taint
                  ) -> Iterable[Finding]:
        short = qual.rsplit(".", 1)[-1]
        for node in taint._stmts():
            if isinstance(node, ast.Call):
                chain = attr_chain(node.func)
                if chain is None:
                    # expression receiver, e.g. (y + 1).tolist()
                    if isinstance(node.func, ast.Attribute) and \
                            node.func.attr in _SYNC_METHODS and \
                            taint.is_tainted(node.func.value):
                        yield Finding(
                            "RPR101", module.path, node.lineno,
                            node.col_offset,
                            f".{node.func.attr}() on a traced value in "
                            f"hot-path function `{short}` forces a "
                            "device sync; use jax.device_get(...)")
                    continue
                if len(chain) == 1 and chain[0] in _SYNC_BUILTINS and \
                        any(taint.is_tainted(a) for a in node.args):
                    yield Finding(
                        "RPR101", module.path, node.lineno,
                        node.col_offset,
                        f"{chain[0]}() on a traced value in hot-path "
                        f"function `{short}` forces a device sync; read "
                        "it via jax.device_get(...) instead")
                elif chain[-1] in _SYNC_METHODS and len(chain) >= 2 and \
                        taint.is_tainted(node.func.value):
                    yield Finding(
                        "RPR101", module.path, node.lineno,
                        node.col_offset,
                        f".{chain[-1]}() on a traced value in hot-path "
                        f"function `{short}` forces a device sync; use "
                        "jax.device_get(...)")
                else:
                    full = taint._full(chain)
                    if full in ("numpy.asarray", "numpy.array",
                                "numpy.copy") and node.args and \
                            taint.is_tainted(node.args[0]):
                        yield Finding(
                            "RPR101", module.path, node.lineno,
                            node.col_offset,
                            f"{'.'.join(chain)}() on a traced value in "
                            f"hot-path function `{short}` is an implicit "
                            "device->host transfer; use "
                            "jax.device_get(...)")
            elif isinstance(node, (ast.If, ast.While)) and \
                    not project.graph.is_jit_target(qual):
                if taint.is_tainted(node.test):
                    yield Finding(
                        "RPR102", module.path, node.lineno,
                        node.col_offset,
                        "truthiness of a traced value in hot-path "
                        f"function `{short}` forces a device sync (and "
                        "raises under jit)")
            elif isinstance(node, ast.Assert) and \
                    not project.graph.is_jit_target(qual):
                if taint.is_tainted(node.test):
                    yield Finding(
                        "RPR102", module.path, node.lineno,
                        node.col_offset,
                        "assert on a traced value in hot-path function "
                        f"`{short}` forces a device sync; use "
                        "checkify or move the check off the hot path")


# ---------------------------------------------------------------------------
class RecompileChecker(Checker):
    """RPR201/RPR202/RPR203: patterns that defeat the jit cache or make
    pytree structure nondeterministic across processes."""

    name = "recompile"
    rules = ("RPR201", "RPR202", "RPR203")

    def check(self, project: Project, module: ModuleInfo
              ) -> Iterable[Finding]:
        for qual, info in _functions_of(module, project):
            taint = None
            for node in ast.walk(info.node):
                if isinstance(node, ast.Call):
                    # jax.jit(f)(...): a fresh jit object every call, so
                    # nothing is ever cached
                    inner = node.func
                    if isinstance(inner, ast.Call):
                        chain = attr_chain(inner.func)
                        if chain is not None:
                            head = project.graph.imports.get(
                                module.name, {}).get(chain[0], chain[0])
                            full = ".".join([head] + chain[1:])
                            if full.split(".")[-1] in \
                                    callgraph._JIT_WRAPPERS:
                                yield Finding(
                                    "RPR201", module.path, node.lineno,
                                    node.col_offset,
                                    "jax.jit(...) invoked immediately — "
                                    "the jit cache is keyed on the "
                                    "wrapper object, so every call "
                                    "recompiles; bind the jitted "
                                    "function once and reuse it")
                if isinstance(node, (ast.If, ast.While)) and \
                        project.graph.is_jit_target(qual):
                    if taint is None:
                        taint = _FunctionTaint(info.node, module, project,
                                               info.class_qual)
                    if taint.is_tainted(node.test):
                        yield Finding(
                            "RPR202", module.path, node.lineno,
                            node.col_offset,
                            "Python branch on a traced value inside "
                            f"jit-traced `{qual.rsplit('.', 1)[-1]}`; "
                            "use lax.cond/lax.select or hoist the "
                            "branch out of the traced function")
        # set-iteration pytree hazards are structural, not per-function
        yield from self._set_iteration(module)

    def _set_iteration(self, module: ModuleInfo) -> Iterable[Finding]:
        # names whose every assignment in this module is a set expression
        # (a single non-set rebinding clears the name)
        set_names: Set[str] = set()
        non_set: Set[str] = set()

        def is_set_expr(node: ast.AST) -> bool:
            if isinstance(node, (ast.Set, ast.SetComp)):
                return True
            if isinstance(node, ast.Call):
                chain = attr_chain(node.func)
                return chain == ["set"]
            if isinstance(node, ast.Name):
                return node.id in set_names
            return False

        for node in ast.walk(module.tree):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                name = node.targets[0].id
                if isinstance(node.value, (ast.Set, ast.SetComp)) or (
                        isinstance(node.value, ast.Call)
                        and attr_chain(node.value.func) == ["set"]):
                    set_names.add(name)
                else:
                    non_set.add(name)
        set_names -= non_set

        for node in ast.walk(module.tree):
            iters: List[ast.AST] = []
            if isinstance(node, ast.For):
                iters.append(node.iter)
            elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                                   ast.GeneratorExp)):
                iters.extend(g.iter for g in node.generators)
            for it in iters:
                if is_set_expr(it):
                    yield Finding(
                        "RPR203", module.path, it.lineno, it.col_offset,
                        "iterating a set to build containers: set order "
                        "is nondeterministic across processes, so pytree "
                        "structure / jit cache keys can drift between "
                        "hosts; iterate sorted(...) instead")


# ---------------------------------------------------------------------------
_ARRAY_ANNOTATIONS = ("jax.Array", "jnp.ndarray", "jax.numpy.ndarray",
                      "chex.Array", "Array")


class PytreeChecker(Checker):
    """RPR301: dataclasses holding jax arrays must be registered
    pytrees, or they cannot flow through jit/scan/shard_map (the plan
    classes are the motivating case)."""

    name = "pytree"
    rules = ("RPR301",)

    def check(self, project: Project, module: ModuleInfo
              ) -> Iterable[Finding]:
        registered = self._registered_names(module)
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            if not self._is_dataclass(node):
                continue
            if node.name in registered:
                continue
            field = self._array_field(node)
            if field is not None:
                yield Finding(
                    "RPR301", module.path, node.lineno, node.col_offset,
                    f"dataclass `{node.name}` holds jax arrays (field "
                    f"`{field}`) but is not a registered pytree; "
                    "decorate with @jax.tree_util."
                    "register_pytree_node_class (or register_dataclass) "
                    "so it can flow through jit/scan/shard_map")

    @staticmethod
    def _is_dataclass(node: ast.ClassDef) -> bool:
        for dec in node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            chain = attr_chain(target)
            if chain and chain[-1] == "dataclass":
                return True
        return False

    @staticmethod
    def _array_field(node: ast.ClassDef) -> Optional[str]:
        for stmt in node.body:
            if isinstance(stmt, ast.AnnAssign) and \
                    isinstance(stmt.target, ast.Name):
                ann = ast.unparse(stmt.annotation)
                base = ann.replace("Optional[", "").replace("]", "")
                if base in _ARRAY_ANNOTATIONS:
                    return stmt.target.id
        return None

    @staticmethod
    def _registered_names(module: ModuleInfo) -> Set[str]:
        out: Set[str] = set()
        for node in ast.walk(module.tree):
            if isinstance(node, ast.ClassDef):
                for dec in node.decorator_list:
                    target = dec.func if isinstance(dec, ast.Call) else dec
                    chain = attr_chain(target)
                    if chain and chain[-1] in (
                            "register_pytree_node_class",
                            "register_dataclass"):
                        out.add(node.name)
            elif isinstance(node, ast.Call):
                chain = attr_chain(node.func)
                if chain and chain[-1] in ("register_pytree_node",
                                           "register_pytree_with_keys",
                                           "register_dataclass") \
                        and node.args:
                    first = attr_chain(node.args[0])
                    if first:
                        out.add(first[-1])
        return out


# ---------------------------------------------------------------------------
class PallasTileChecker(Checker):
    """RPR401/RPR402/RPR403: BlockSpec register-tile alignment,
    interpret defaults in library code, and a name on every kernel."""

    name = "pallas-tile"
    rules = ("RPR401", "RPR402", "RPR403")

    def check(self, project: Project, module: ModuleInfo
              ) -> Iterable[Finding]:
        yield from self._block_specs(module)
        yield from self._interpret_defaults(module)
        yield from self._kernel_names(module)

    def _kernel_names(self, module: ModuleInfo) -> Iterable[Finding]:
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call) and \
                    (attr_chain(node.func) or [""])[-1] == "pallas_call" \
                    and not any(k.arg == "name" for k in node.keywords):
                yield Finding(
                    "RPR403", module.path, node.lineno, node.col_offset,
                    "pallas_call without name=: a device trace names the "
                    "kernel after its jitted wrapper, which a refactor "
                    "renames; pass name=<kernel family>")

    def _block_specs(self, module: ModuleInfo) -> Iterable[Finding]:
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            chain = attr_chain(node.func)
            if not chain or chain[-1] != "BlockSpec":
                continue
            if any(k.arg == "memory_space" for k in node.keywords):
                continue              # SMEM/scalar specs: no lane tiling
            if not node.args or not isinstance(node.args[0], ast.Tuple):
                continue
            shape = node.args[0].elts
            if len(shape) < 2:
                continue
            minor = self._resolve_int(shape[-1], module)
            if minor is not None and minor % LANE != 0:
                yield Finding(
                    "RPR401", module.path, node.lineno, node.col_offset,
                    f"BlockSpec minor dim {minor} is not a multiple of "
                    f"the {LANE}-lane register tile; compiled Mosaic "
                    "needs lane-aligned operands (pad a width-1 vector "
                    "to a full tile, as pim_matmul_fused_pallas does "
                    "with its scales)")

    @staticmethod
    def _resolve_int(node: ast.AST, module: ModuleInfo) -> Optional[int]:
        if isinstance(node, ast.Constant) and isinstance(node.value, int) \
                and not isinstance(node.value, bool):
            return node.value
        if isinstance(node, ast.Name):
            return module.int_constants.get(node.id)
        return None

    def _interpret_defaults(self, module: ModuleInfo) -> Iterable[Finding]:
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                args = node.args
                pos = args.posonlyargs + args.args
                defaults = [None] * (len(pos) - len(args.defaults)) + \
                    list(args.defaults)
                pairs = list(zip(pos, defaults)) + \
                    list(zip(args.kwonlyargs, args.kw_defaults))
                for arg, default in pairs:
                    if arg.arg == "interpret" and \
                            isinstance(default, ast.Constant) and \
                            default.value is True:
                        yield Finding(
                            "RPR402", module.path, default.lineno,
                            default.col_offset,
                            f"`{node.name}` defaults interpret=True: "
                            "library code must not silently run the "
                            "Pallas interpreter on real hardware; "
                            "default to None and resolve per backend "
                            "(kernels.runtime.resolve_interpret)")
            elif isinstance(node, ast.ClassDef):
                for stmt in node.body:
                    if isinstance(stmt, ast.AnnAssign) and \
                            isinstance(stmt.target, ast.Name) and \
                            stmt.target.id == "interpret" and \
                            isinstance(stmt.value, ast.Constant) and \
                            stmt.value.value is True:
                        yield Finding(
                            "RPR402", module.path, stmt.lineno,
                            stmt.col_offset,
                            f"`{node.name}.interpret` defaults to True: "
                            "default to None and resolve per backend "
                            "(kernels.runtime.resolve_interpret)")


register_checker(HostSyncChecker())
register_checker(RecompileChecker())
register_checker(PytreeChecker())
register_checker(PallasTileChecker())
