"""AST index shared by every graftlint rule.

One parse of the repo produces, per module: the classes, their lock
attributes (``self._x = threading.Lock()`` / ``make_lock(...)``), their
thread entry points (``threading.Thread(target=self._loop)`` and
local-closure targets), constructor-based attribute types
(``self.pool = TargetPool(...)`` — the one-level cross-class link R1–R3
propagate through), and, per function, a flat event stream of
``(ast-node, lockset-held)`` pairs plus the ordered lock acquisitions.

The lockset walker is deliberately syntactic: a lock is "held" inside a
``with self._lock:`` / ``with MODULE_LOCK:`` block over an attribute or
name the index recognized as lock-typed. Nested ``def``/``lambda``
bodies are excluded from the enclosing lockset (they run later, on
whatever thread calls them); a nested function handed to
``threading.Thread(target=...)`` is indexed as its own thread-entry
function instead.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field

LOCK_CTORS = {"Lock", "RLock", "Condition", "Semaphore", "BoundedSemaphore"}
LOCK_FACTORIES = {"make_lock", "make_rlock"}

# attribute types whose mutator methods are atomic under the GIL (CPython
# deque/queue) or are synchronization objects themselves — R1 does not
# require a lock around their method calls
SAFE_CTORS = {"deque", "Queue", "SimpleQueue", "LifoQueue", "PriorityQueue",
              "Event", "Semaphore", "BoundedSemaphore", "Barrier"}

MUTATORS = {"add", "append", "appendleft", "extend", "insert", "pop",
            "popleft", "popitem", "remove", "discard", "clear", "update",
            "setdefault", "__setitem__"}


def call_name(call: ast.Call) -> "str | None":
    """Last identifier of a call's function: ``a.b.c(...)`` -> ``c``."""
    f = call.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return None


def _is_lock_ctor(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and call_name(node) in (LOCK_CTORS | LOCK_FACTORIES))


def is_self_attr(node: ast.AST) -> "str | None":
    """``self.X`` -> ``"X"``, else None."""
    if (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"):
        return node.attr
    return None


@dataclass
class FuncInfo:
    qualname: str                    # "Class.method" / "func" / "C.m.<f>"
    name: str
    node: ast.AST
    relpath: str
    cls: "ClassInfo | None" = None
    is_init: bool = False
    # (node, held-lockset) for every expression/simple-statement node
    events: list = field(default_factory=list)
    # (lock-id, held-lockset-before, lineno) in source order
    acquires: list = field(default_factory=list)

    @property
    def lineno(self) -> int:
        return self.node.lineno

    # -- derived views (cached) -------------------------------------- #

    def self_writes(self) -> "list[tuple[str, tuple, int, str]]":
        """(attr, lockset, lineno, how) for every write to ``self.X``:
        assignment, augmented assignment, ``self.X[...] = v``, or a
        mutator-method call (``self.X.append(...)``)."""
        cached = getattr(self, "_writes", None)
        if cached is not None:
            return cached
        out = []
        safe = self.cls.safe_attrs if self.cls else set()

        def tgt(node, held, lineno, how):
            attr = is_self_attr(node)
            if attr is not None:
                out.append((attr, held, lineno, how))
            elif isinstance(node, ast.Subscript):
                attr = is_self_attr(node.value)
                if attr is not None:
                    out.append((attr, held, lineno, "item"))
            elif isinstance(node, (ast.Tuple, ast.List)):
                for el in node.elts:
                    tgt(el, held, lineno, how)

        for node, held in self.events:
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    tgt(t, held, node.lineno, "assign")
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                if getattr(node, "value", True) is not None:
                    tgt(node.target, held, node.lineno, "assign")
            elif isinstance(node, ast.Call):
                f = node.func
                if (isinstance(f, ast.Attribute) and f.attr in MUTATORS):
                    attr = is_self_attr(f.value)
                    if attr is not None and attr not in safe:
                        out.append((attr, held, node.lineno, "mutate"))
        self._writes = out
        return out

    def self_reads(self) -> "set[str]":
        """Attrs of ``self`` loaded anywhere in the function."""
        cached = getattr(self, "_reads", None)
        if cached is not None:
            return cached
        out = set()
        for node, _held in self.events:
            for sub in ast.walk(node):
                if (isinstance(sub, ast.Attribute)
                        and isinstance(sub.ctx, ast.Load)):
                    attr = is_self_attr(sub)
                    if attr is not None:
                        out.add(attr)
        self._reads = out
        return out

    def self_calls(self) -> "list[tuple[str, tuple, int]]":
        """(method, lockset, lineno) for every ``self.m(...)`` call."""
        cached = getattr(self, "_scalls", None)
        if cached is not None:
            return cached
        out = []
        for node, held in self.events:
            if isinstance(node, ast.Call):
                attr = is_self_attr(node.func)
                if attr is not None:
                    out.append((attr, held, node.lineno))
        self._scalls = out
        return out

    def attr_calls(self) -> "list[tuple[str, str, tuple, int]]":
        """(attr, method, lockset, lineno) for ``self.X.m(...)`` calls —
        the cross-class propagation sites."""
        cached = getattr(self, "_acalls", None)
        if cached is not None:
            return cached
        out = []
        for node, held in self.events:
            if isinstance(node, ast.Call) and isinstance(node.func,
                                                        ast.Attribute):
                attr = is_self_attr(node.func.value)
                if attr is not None:
                    out.append((attr, node.func.attr, held, node.lineno))
        self._acalls = out
        return out


@dataclass
class ClassInfo:
    name: str
    node: ast.ClassDef
    relpath: str
    lock_attrs: set = field(default_factory=set)
    safe_attrs: set = field(default_factory=set)
    attr_types: dict = field(default_factory=dict)   # attr -> class name
    funcs: dict = field(default_factory=dict)        # name -> FuncInfo
    thread_targets: set = field(default_factory=set)  # names into funcs


@dataclass
class ModuleInfo:
    relpath: str
    path: str
    tree: ast.Module
    classes: dict = field(default_factory=dict)
    functions: dict = field(default_factory=dict)
    module_locks: set = field(default_factory=set)

    @property
    def stem(self) -> str:
        return os.path.basename(self.relpath)


@dataclass
class Index:
    root: str
    modules: list
    classes_by_name: dict = field(default_factory=dict)

    def all_funcs(self):
        for mod in self.modules:
            for fn in mod.functions.values():
                yield mod, fn
            for cls in mod.classes.values():
                for fn in cls.funcs.values():
                    yield mod, fn


# -- lockset walking ------------------------------------------------------ #


def _scan_func(fninfo: FuncInfo, module: ModuleInfo) -> None:
    """Populate events + acquires for one function."""
    cls = fninfo.cls
    local_locks = set()
    for node in ast.walk(fninfo.node):
        if (isinstance(node, ast.Assign) and _is_lock_ctor(node.value)):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    local_locks.add(t.id)

    def lock_id(expr: ast.AST) -> "str | None":
        attr = is_self_attr(expr)
        if attr is not None and cls is not None and attr in cls.lock_attrs:
            return f"{cls.name}.{attr}"
        if isinstance(expr, ast.Name):
            if expr.id in module.module_locks:
                return f"{module.stem}:{expr.id}"
            if expr.id in local_locks:
                return f"{fninfo.qualname}:{expr.id}"
        return None

    events, acquires = fninfo.events, fninfo.acquires

    def walk(node: ast.AST, held: list) -> None:
        if isinstance(node, (ast.With, ast.AsyncWith)):
            newly: list = []
            for item in node.items:
                walk(item.context_expr, held + newly)
                lid = lock_id(item.context_expr)
                if lid is not None:
                    acquires.append((lid, tuple(held + newly),
                                     item.context_expr.lineno))
                    newly.append(lid)
            for st in node.body:
                walk(st, held + newly)
            return
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda, ast.ClassDef)):
            return                      # different execution context
        events.append((node, tuple(held)))
        for child in ast.iter_child_nodes(node):
            walk(child, held)

    body = fninfo.node.body if hasattr(fninfo.node, "body") else []
    for st in body:
        walk(st, [])


def _thread_target_names(call: ast.Call) -> "list[ast.AST]":
    """target= expressions of a ``threading.Thread(...)`` construction."""
    if call_name(call) != "Thread":
        return []
    return [kw.value for kw in call.keywords if kw.arg == "target"]


def _index_class(node: ast.ClassDef, module: ModuleInfo) -> ClassInfo:
    cls = ClassInfo(name=node.name, node=node, relpath=module.relpath)
    methods = [st for st in node.body
               if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef))]

    def _ann_class(ann: "ast.AST | None") -> "str | None":
        """First class-like identifier of a parameter annotation —
        handles ``Foo``, ``"Foo | None"``, ``Optional[Foo]``."""
        if ann is None:
            return None
        if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
            head = ann.value.split("|")[0].strip().split("[")[0].strip()
            return head if head.lstrip("_")[:1].isupper() else None
        if isinstance(ann, ast.Name):
            return ann.id if ann.id.lstrip("_")[:1].isupper() else None
        if isinstance(ann, ast.Subscript):
            return _ann_class(ann.slice)
        if isinstance(ann, ast.BinOp):
            return _ann_class(ann.left)
        return None

    # pass 1: locks, attr types, thread targets, nested-closure targets
    nested_targets: list = []           # (method, nested FunctionDef)
    for m in methods:
        param_types = {a.arg: _ann_class(a.annotation)
                       for a in (m.args.posonlyargs + m.args.args
                                 + m.args.kwonlyargs)}
        local_defs = {st.name: st for st in ast.walk(m)
                      if isinstance(st, ast.FunctionDef) and st is not m}
        for sub in ast.walk(m):
            if isinstance(sub, (ast.Assign, ast.AnnAssign)):
                targets = (sub.targets if isinstance(sub, ast.Assign)
                           else [sub.target])
                for t in targets:
                    attr = is_self_attr(t)
                    if attr is None:
                        continue
                    val = sub.value
                    if _is_lock_ctor(val):
                        cls.lock_attrs.add(attr)
                    elif isinstance(val, ast.Call):
                        ctor = call_name(val)
                        if ctor in SAFE_CTORS:
                            cls.safe_attrs.add(attr)
                        elif ctor and ctor.lstrip("_")[:1].isupper():
                            cls.attr_types[attr] = ctor
                    elif (isinstance(val, ast.Name)
                          and param_types.get(val.id)):
                        cls.attr_types[attr] = param_types[val.id]
            elif isinstance(sub, ast.Call):
                for tgt in _thread_target_names(sub):
                    attr = is_self_attr(tgt)
                    if attr is not None:
                        cls.thread_targets.add(attr)
                    elif (isinstance(tgt, ast.Name)
                          and tgt.id in local_defs):
                        nested_targets.append((m, local_defs[tgt.id]))

    # pass 2: per-function events
    for m in methods:
        fi = FuncInfo(qualname=f"{cls.name}.{m.name}", name=m.name,
                      node=m, relpath=module.relpath, cls=cls,
                      is_init=(m.name == "__init__"))
        _scan_func(fi, module)
        cls.funcs[m.name] = fi
    for host, nd in nested_targets:
        qual = f"{cls.name}.{host.name}.{nd.name}"
        fi = FuncInfo(qualname=qual, name=qual, node=nd,
                      relpath=module.relpath, cls=cls)
        _scan_func(fi, module)
        cls.funcs[qual] = fi
        cls.thread_targets.add(qual)
    return cls


def index_module(path: str, relpath: str, source: "str | None" = None
                 ) -> "ModuleInfo | None":
    if source is None:
        try:
            with open(path, encoding="utf-8") as fh:
                source = fh.read()
        except OSError:
            return None
    try:
        tree = ast.parse(source, filename=relpath)
    except SyntaxError:
        return None
    mod = ModuleInfo(relpath=relpath, path=path, tree=tree)
    for st in tree.body:
        if isinstance(st, ast.Assign) and _is_lock_ctor(st.value):
            for t in st.targets:
                if isinstance(t, ast.Name):
                    mod.module_locks.add(t.id)
    for st in tree.body:
        if isinstance(st, ast.ClassDef):
            mod.classes[st.name] = _index_class(st, mod)
        elif isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fi = FuncInfo(qualname=st.name, name=st.name, node=st,
                          relpath=relpath)
            _scan_func(fi, mod)
            mod.functions[st.name] = fi
    return mod


def build_index(root: str, scan: "list[str] | None" = None) -> Index:
    if scan is None:
        scan = [os.path.join(root, "mmlspark_tpu")]
    paths = []
    for entry in scan:
        if os.path.isfile(entry):
            paths.append(entry)
            continue
        for base, _dirs, names in os.walk(entry):
            paths.extend(os.path.join(base, n) for n in names
                         if n.endswith(".py"))
    idx = Index(root=root, modules=[])
    for path in sorted(paths):
        mod = index_module(path, os.path.relpath(path, root))
        if mod is None:
            continue
        idx.modules.append(mod)
        for cls in mod.classes.values():
            idx.classes_by_name.setdefault(cls.name, cls)
    return idx


def index_source(source: str, relpath: str = "selftest.py") -> Index:
    """Single-module index for rule selftests."""
    idx = Index(root=".", modules=[])
    mod = index_module(relpath, relpath, source=source)
    if mod is not None:
        idx.modules.append(mod)
        for cls in mod.classes.values():
            idx.classes_by_name.setdefault(cls.name, cls)
    return idx


# -- fixpoints shared by R1/R2/R3 ----------------------------------------- #


def thread_reachable(idx: Index) -> "dict[int, set[str]]":
    """Per-class (keyed by id(ClassInfo)) set of func names reachable
    from a thread entry point, propagated through ``self.m()`` calls and
    one level of ``self.X.m()`` across constructor-typed attributes."""
    reach: dict[int, set[str]] = {}

    def close_over_self_calls(cls: ClassInfo, seed: "set[str]") -> set:
        out = set(seed)
        frontier = list(seed)
        while frontier:
            fname = frontier.pop()
            fi = cls.funcs.get(fname)
            if fi is None:
                continue
            for callee, _held, _ln in fi.self_calls():
                if callee in cls.funcs and callee not in out:
                    out.add(callee)
                    frontier.append(callee)
        return out

    all_classes = [cls for mod in idx.modules
                   for cls in mod.classes.values()]
    for cls in all_classes:
        reach[id(cls)] = close_over_self_calls(cls, cls.thread_targets)

    # one level across classes: a thread-reachable method calling
    # self.X.m() makes C2.m (X: C2) thread-reachable in C2
    for cls in all_classes:
        for fname in list(reach[id(cls)]):
            fi = cls.funcs.get(fname)
            if fi is None:
                continue
            for attr, meth, _held, _ln in fi.attr_calls():
                tname = cls.attr_types.get(attr)
                target = idx.classes_by_name.get(tname) if tname else None
                if target is not None and meth in target.funcs:
                    reach[id(target)] = close_over_self_calls(
                        target, reach[id(target)] | {meth})
    return reach


def caller_context(cls: ClassInfo) -> "tuple[set, dict]":
    """(init_phase, inherited) for one class.

    ``init_phase``: func names that only ever run during construction —
    ``__init__`` plus private helpers reachable ONLY from init-phase
    callers. Their writes predate any concurrency, so R1 skips them.

    ``inherited``: private-helper name -> lockset guaranteed held at
    EVERY (non-init) internal call site — the static analogue of
    Eraser's lockset refinement. A helper like ``_tick`` that is only
    invoked under ``self._lock`` is guarded even though its own body
    shows no ``with``. Public methods and thread entry points inherit
    nothing (they are externally callable)."""
    sites: dict[str, list] = {n: [] for n in cls.funcs}
    for caller, fi in cls.funcs.items():
        for callee, held, _ln in fi.self_calls():
            if callee in sites:
                sites[callee].append((caller, frozenset(held)))

    def private(n: str) -> bool:
        leaf = n.rsplit(".", 1)[-1]
        return leaf.startswith("_") and not (leaf.startswith("__")
                                             and leaf.endswith("__"))

    init_phase: set = {n for n, fi in cls.funcs.items() if fi.is_init}
    changed = True
    while changed:
        changed = False
        for n in cls.funcs:
            if (n in init_phase or not private(n)
                    or n in cls.thread_targets or not sites[n]):
                continue
            if all(c in init_phase for c, _h in sites[n]):
                init_phase.add(n)
                changed = True

    inherited: dict = {}
    eligible = [n for n in cls.funcs
                if private(n) and sites[n] and n not in cls.thread_targets
                and n not in init_phase]
    changed = True
    while changed:
        changed = False
        for n in eligible:
            non_init = [(c, h) for c, h in sites[n]
                        if c not in init_phase]
            if not non_init:
                continue
            new = None
            for c, h in non_init:
                ci = inherited.get(c, frozenset())
                v = h | ci
                new = v if new is None else (new & v)
            if new != inherited.get(n, frozenset()):
                inherited[n] = new
                changed = True
    return init_phase, inherited


def transitive_acquires(cls: ClassInfo) -> "dict[str, set[str]]":
    """func name -> lock ids acquired by the func or any self-callee."""
    direct = {n: {lid for lid, _h, _ln in fi.acquires}
              for n, fi in cls.funcs.items()}
    changed = True
    while changed:
        changed = False
        for n, fi in cls.funcs.items():
            for callee, _h, _ln in fi.self_calls():
                extra = direct.get(callee)
                if extra and not extra <= direct[n]:
                    direct[n] |= extra
                    changed = True
    return direct


def transitive_blocking(cls: ClassInfo, direct_ops) -> "dict[str, set]":
    """func name -> {(op, lineno)} blocking ops in the func or any
    self-callee. `direct_ops(fi)` yields (op, lineno) pairs."""
    table = {n: set(direct_ops(fi)) for n, fi in cls.funcs.items()}
    changed = True
    while changed:
        changed = False
        for n, fi in cls.funcs.items():
            for callee, _h, _ln in fi.self_calls():
                extra = table.get(callee)
                if extra and not extra <= table[n]:
                    table[n] |= extra
                    changed = True
    return table
