"""Functional module system — the TPU-native successor of the reference's layer graph.

The reference builds models as a C++ ``Layer`` graph driven by protobuf configs
(``paddle/gserver/layers/Layer.h:62``, ``python/paddle/trainer/config_parser.py``).
Here a model is a tree of :class:`Module` objects that produce *pure functions*:

    net = Linear(10)
    variables = net.init(rng, x)          # {'params': {...}, 'state': {...}}
    y = net.apply(variables, x)           # pure — safe under jax.jit / pjit / grad

Parameters live in a plain nested-dict pytree, so every JAX transform
(``jit``/``grad``/``vmap``/``pjit``/``shard_map``) applies directly; sharding a model
over a TPU mesh is just sharding this pytree (see ``paddle_tpu.parallel``).

Mutable collections ("state", e.g. BatchNorm running stats — the analog of the
reference's ``Parameter`` typed buffers, ``paddle/parameter/Parameter.h:60``) are
threaded functionally: ``apply(..., mutable=('state',))`` returns
``(out, updated_variables)``.
"""

from __future__ import annotations

import threading
import zlib
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from . import initializers as init_lib

__all__ = ["Module", "Sequential", "current_rng", "no_params",
           "is_initializing"]


class ModuleError(Exception):
    pass


class _Frame:
    """Per-init/apply execution context (thread-local)."""

    __slots__ = ("variables", "rngs", "mode", "mutable", "path", "counters",
                 "rng_counters", "touched", "active")

    def __init__(self, variables, rngs, mode, mutable):
        self.variables = variables          # {'params': nested, 'state': nested, ...}
        self.rngs = dict(rngs or {})        # {'params': key, 'dropout': key, ...}
        self.mode = mode                    # 'init' | 'apply'
        self.mutable = frozenset(mutable)
        self.path: list[str] = []
        self.counters: Dict[Tuple[str, ...], Dict[str, int]] = {}
        self.rng_counters: Dict[Tuple[str, ...], int] = {}
        self.touched = False                # any state write happened
        self.active: list[int] = []         # module-instance id stack


_tls = threading.local()


def _frame() -> _Frame:
    fr = getattr(_tls, "frame", None)
    if fr is None:
        raise ModuleError(
            "Module methods that access parameters must run under "
            "Module.init(...) or Module.apply(...).")
    return fr


def _get_node(tree: dict, path: Sequence[str], create: bool) -> dict:
    node = tree
    for p in path:
        if p not in node:
            if not create:
                raise KeyError("/".join(path))
            node[p] = {}
        node = node[p]
    return node


def is_initializing() -> bool:
    """True while tracing under ``Module.init`` (parameter creation), False
    under ``apply`` or outside any module frame. Lets a forward() choose a
    trace-only fast path (e.g. the rematerialized scan-over-layers in
    ``models/transformer.py``) that cannot create parameters itself."""
    fr = getattr(_tls, "frame", None)
    return fr is not None and fr.mode == "init"


def current_rng(kind: str = "dropout") -> jax.Array:
    """Fetch a fresh RNG key of the given kind inside forward()."""
    fr = _frame()
    if kind not in fr.rngs:
        if fr.mode == "init" and "params" in fr.rngs:
            # During init any stream derives from the main key — init(train=True)
            # with dropout must not force the caller to thread extra rngs.
            fr.rngs[kind] = jax.random.fold_in(
                fr.rngs["params"], zlib.crc32(kind.encode()) & 0x7FFFFFFF)
        else:
            raise ModuleError(
                f"rng '{kind}' requested but not provided; pass "
                f"rngs={{'{kind}': key}} to init/apply")
    path = tuple(fr.path)
    cnt = fr.rng_counters.get((kind,) + path, 0)
    fr.rng_counters[(kind,) + path] = cnt + 1
    key = fr.rngs[kind]
    # Deterministic per-path derivation. Must be stable across processes (multi-host
    # SPMD inits the same params everywhere), so use crc32, not salted hash().
    h = zlib.crc32("/".join((kind,) + path + (str(cnt),)).encode()) & 0x7FFFFFFF
    return jax.random.fold_in(key, h)


class Module:
    """Base class. Subclasses define hyperparameters in ``__init__`` (always call
    ``super().__init__()``) and computation in ``forward(*args, **kwargs)``.

    Submodules may be created in ``__init__`` (preferred — attribute name becomes
    the parameter-tree key) or inline in ``forward`` (auto-named ``Cls_i``).
    Calling the same Module instance twice shares its parameters (the reference's
    parameter-sharing via shared ``ParameterConfig`` names).
    """

    def __init__(self, name: Optional[str] = None):
        object.__setattr__(self, "_name", name)

    def __init_subclass__(cls, **kw):
        """Every Module subclass auto-registers in the model-IR registry and
        records its constructor args on instantiation, making any model
        serializable to a config ("config is data" — the reference's
        ModelConfig contract, ``proto/ModelConfig.proto:656``; see
        ``paddle_tpu.core.config``)."""
        super().__init_subclass__(**kw)
        import functools

        from . import config as _config
        if "<locals>" not in cls.__qualname__:
            _config.register_module(cls)
        orig = cls.__init__

        @functools.wraps(orig)
        def recording_init(self, *args, **kwargs):
            if not hasattr(self, "_init_record"):   # outermost subclass wins
                object.__setattr__(self, "_init_record",
                                   {"cls": cls, "args": args,
                                    "kwargs": kwargs})
            orig(self, *args, **kwargs)

        cls.__init__ = recording_init

    # -- naming ---------------------------------------------------------------

    def __setattr__(self, key, value):
        if isinstance(value, Module) and getattr(value, "_name", None) is None:
            object.__setattr__(value, "_name", key)
        elif isinstance(value, (list, tuple)):
            for i, v in enumerate(value):
                if isinstance(v, Module) and getattr(v, "_name", None) is None:
                    object.__setattr__(v, "_name", f"{key}_{i}")
        object.__setattr__(self, key, value)

    def _ensure_name(self, fr: _Frame) -> str:
        if self._name is None:
            level = fr.counters.setdefault(tuple(fr.path), {})
            cls = type(self).__name__
            idx = level.get(cls, 0)
            level[cls] = idx + 1
            object.__setattr__(self, "_name", f"{cls}_{idx}")
        return self._name

    # -- variable access ------------------------------------------------------

    def param(self, name: str, init: Callable, shape: Sequence[int] = (),
              dtype=None) -> jax.Array:
        """Declare/fetch a trainable parameter at the current path."""
        fr = _frame()
        coll = fr.variables.setdefault("params", {})
        node = _get_node(coll, fr.path, create=(fr.mode == "init"))
        if fr.mode == "init" and name not in node:
            rng = current_rng("params")
            node[name] = init(rng, tuple(shape), dtype or jnp.float32)
        if name not in node:
            raise ModuleError(f"missing param {'/'.join(fr.path + [name])}")
        return node[name]

    def state(self, name: str, init: Callable, shape: Sequence[int] = (),
              dtype=None) -> jax.Array:
        """Declare/fetch a non-trainable state variable (running stats etc.)."""
        fr = _frame()
        coll = fr.variables.setdefault("state", {})
        node = _get_node(coll, fr.path, create=(fr.mode == "init"))
        if fr.mode == "init" and name not in node:
            if callable(init):
                import inspect
                try:
                    nargs = len(inspect.signature(init).parameters)
                except (TypeError, ValueError):
                    nargs = 3
                if nargs == 0:
                    node[name] = init()
                else:
                    node[name] = init(jax.random.PRNGKey(0), tuple(shape),
                                      dtype or jnp.float32)
            else:
                node[name] = init
        if name not in node:
            raise ModuleError(f"missing state {'/'.join(fr.path + [name])}")
        return node[name]

    def subtree(self, collection: str = "params"):
        """This submodule's raw ``collection`` subtree — callable from the
        PARENT's forward, without entering the submodule's scope. The scan/
        remat paths use it to stack homogeneous sibling submodules' params
        ([L, ...] leading layer axis) and re-apply one submodule over the
        stack (``models/transformer.py``). From inside this module's own
        scope (a method run by ``apply(method=)``) it is the module's own
        subtree: like :meth:`scope`, an active instance adds no segment."""
        fr = _frame()
        path = list(fr.path)
        if not (fr.active and fr.active[-1] == id(self)):
            path.append(self._ensure_name(fr))
        return _get_node(fr.variables.get(collection, {}), path,
                         create=False)

    def update_state(self, name: str, value: jax.Array) -> None:
        """Write a state variable. No-op outside init unless 'state' is mutable."""
        fr = _frame()
        if fr.mode == "apply" and "state" not in fr.mutable:
            return
        coll = fr.variables.setdefault("state", {})
        node = _get_node(coll, fr.path, create=True)
        node[name] = value
        fr.touched = True

    # -- execution ------------------------------------------------------------

    def scope(self):
        """Context manager entering this module's parameter scope — for methods
        other than forward() that declare/fetch params (e.g. RNN cell ``step``,
        ``Embedding.table``, ``CRF.weights``). Idempotent: if this instance's
        scope is already active (we're inside its forward or another scoped
        method), no extra path segment is pushed — so helper methods can wrap
        themselves in scope() and be callable both internally and externally."""
        import contextlib

        @contextlib.contextmanager
        def _scope():
            fr = _frame()
            if fr.active and fr.active[-1] == id(self):
                yield self
                return
            name = self._ensure_name(fr)
            fr.path.append(name)
            fr.active.append(id(self))
            fr.counters[tuple(fr.path)] = {}
            try:
                yield self
            finally:
                fr.path.pop()
                fr.active.pop()
        return _scope()

    def __call__(self, *args, **kwargs):
        fr = _frame()
        name = self._ensure_name(fr)
        fr.path.append(name)
        fr.active.append(id(self))
        fr.counters[tuple(fr.path)] = {}
        try:
            return self.forward(*args, **kwargs)
        finally:
            fr.path.pop()
            fr.active.pop()

    def forward(self, *args, **kwargs):  # pragma: no cover - abstract
        raise NotImplementedError

    def init(self, rng, *args, rngs: Optional[dict] = None, **kwargs):
        """Run forward once, creating all variables. Returns the variables dict."""
        all_rngs = {"params": rng}
        if rngs:
            all_rngs.update(rngs)
        fr = _Frame({}, all_rngs, "init", mutable=("params", "state"))
        prev = getattr(_tls, "frame", None)
        _tls.frame = fr
        try:
            self(*args, **kwargs)
        finally:
            _tls.frame = prev
        fr.variables.setdefault("params", {})
        fr.variables.setdefault("state", {})
        return fr.variables

    def apply(self, variables, *args, rngs: Optional[dict] = None,
              mutable: Sequence[str] = (), method=None, **kwargs):
        """Pure application. With ``mutable`` non-empty returns (out, new_vars).

        ``method`` names (or is) an alternative entry point — e.g.
        ``model.apply(vs, x, method="generate")`` for beam search or
        ``crf.apply(vs, em, lengths, method="decode")`` — executed inside this
        module's parameter scope.
        """
        if isinstance(mutable, str):
            mutable = (mutable,)
        # Shallow-copy the mutable collections so writes don't alias caller state.
        vs = dict(variables)
        for c in mutable:
            vs[c] = jax.tree_util.tree_map(lambda x: x, vs.get(c, {}))
        fr = _Frame(vs, rngs, "apply", mutable=mutable)
        prev = getattr(_tls, "frame", None)
        _tls.frame = fr
        try:
            if method is None:
                out = self(*args, **kwargs)
            else:
                fn = getattr(self, method) if isinstance(method, str) else method
                with self.scope():
                    out = fn(*args, **kwargs)
        finally:
            _tls.frame = prev
        if mutable:
            return out, {c: fr.variables.get(c, {}) for c in mutable}
        return out


class Sequential(Module):
    """Chain of modules applied in order (the reference's linear layer stacking)."""

    def __init__(self, *layers: Module, name: Optional[str] = None):
        super().__init__(name=name)
        self.layers = list(layers)

    def forward(self, x, **kwargs):
        for layer in self.layers:
            x = layer(x, **_filter_kwargs(layer, kwargs))
        return x


def _filter_kwargs(mod: Module, kwargs: dict) -> dict:
    """Keep only kwargs the layer's forward can accept (by name or **kwargs)."""
    if not kwargs:
        return kwargs
    import inspect
    try:
        sig = inspect.signature(mod.forward)
    except (TypeError, ValueError):
        return {}
    if any(p.kind == inspect.Parameter.VAR_KEYWORD
           for p in sig.parameters.values()):
        return kwargs
    names = {p.name for p in sig.parameters.values()
             if p.kind in (inspect.Parameter.POSITIONAL_OR_KEYWORD,
                           inspect.Parameter.KEYWORD_ONLY)}
    return {k: v for k, v in kwargs.items() if k in names}


def no_params(fn: Callable) -> Callable:
    """Wrap a pure function as a Module-compatible callable."""
    class _Fn(Module):
        def forward(self, *a, **k):
            return fn(*a, **k)
    m = _Fn(name=getattr(fn, "__name__", "fn"))
    return m
