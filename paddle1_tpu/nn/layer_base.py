"""nn.Layer — the module system.

Analog of the reference's dygraph Layer
(/root/reference/python/paddle/fluid/dygraph/layers.py:80 Layer, :875
state_dict) and the 2.0 ``paddle.nn.Layer``. Parameters are
``core.Parameter`` tensors registered by attribute assignment; sublayers
nest; forward/backward hooks, train/eval mode, ``apply``, ``to`` and
state_dict round-trips match the reference semantics.

TPU-native addition: ``functional_state`` / ``load_functional_state`` — the
bridge that lets a Layer's forward be traced by jax transforms (jit/grad/
shard_map) with parameters passed functionally; this is what the compiled
(static-analog) mode builds on.
"""

from __future__ import annotations

import contextlib
from collections import OrderedDict
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax
import numpy as np

from ..autograd.engine import _tls as _trace_tls
from ..core.errors import InvalidArgumentError, NotFoundError
from ..core.tensor import Parameter, Tensor, to_tensor
from ..core import dtype as dtypes

__all__ = ["Layer"]

_global_layer_name_counts: Dict[str, int] = {}

# live registry of named parameters/buffers for the variable-scope
# surface (static.global_scope().find_var(name) — reference Scope
# lookup of persistable vars); weak so layers still garbage-collect
import weakref as _weakref
_named_variables: "_weakref.WeakValueDictionary" = \
    _weakref.WeakValueDictionary()


def _unique_name(prefix: str) -> str:
    n = _global_layer_name_counts.get(prefix, 0)
    _global_layer_name_counts[prefix] = n + 1
    return f"{prefix}_{n}"


class HookRemoveHelper:
    def __init__(self, hooks: OrderedDict, hook_id: int):
        self._hooks = hooks
        self._id = hook_id

    def remove(self):
        self._hooks.pop(self._id, None)


class Layer:
    """Base class for all network layers."""

    def __init__(self, name_scope: Optional[str] = None, dtype="float32"):
        self._full_name = _unique_name(
            name_scope or self.__class__.__name__.lower())
        self._dtype = dtypes.convert_dtype(dtype)
        self.training = True
        self._parameters: "OrderedDict[str, Parameter]" = OrderedDict()
        self._sub_layers: "OrderedDict[str, Layer]" = OrderedDict()
        self._buffers: "OrderedDict[str, Optional[Tensor]]" = OrderedDict()
        self._non_persistable_buffer_names = set()
        self._forward_pre_hooks: "OrderedDict[int, Callable]" = OrderedDict()
        self._forward_post_hooks: "OrderedDict[int, Callable]" = OrderedDict()
        self._hook_id = 0
        self._casted_by_pure_fp16 = False

    # -- naming -------------------------------------------------------------

    def full_name(self) -> str:
        return self._full_name

    # -- parameter / buffer / sublayer registration -------------------------

    def add_parameter(self, name: str, parameter: Optional[Parameter]):
        if "." in name or name == "":
            raise InvalidArgumentError(f"Bad parameter name: {name!r}")
        if parameter is not None and not isinstance(parameter, Parameter):
            raise InvalidArgumentError(
                f"add_parameter expects Parameter, got {type(parameter)}")
        self._parameters[name] = parameter
        if parameter is not None and parameter.name is None:
            parameter.name = f"{self._full_name}.{name}"
        if parameter is not None and parameter.name:
            _named_variables[parameter.name] = parameter
        return parameter

    def add_sublayer(self, name: str, sublayer: "Layer"):
        if not isinstance(sublayer, Layer):
            raise InvalidArgumentError(
                f"add_sublayer expects Layer, got {type(sublayer)}")
        self._sub_layers[str(name)] = sublayer
        sublayer.__dict__["_scope_name"] = str(name)
        return sublayer

    def register_buffer(self, name: str, tensor: Optional[Tensor],
                        persistable: bool = True):
        if "." in name or name == "":
            raise InvalidArgumentError(f"Bad buffer name: {name!r}")
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        elif tensor is not None:
            # persistable buffers are scope-visible variables in the
            # reference (BN running stats live in the Scope)
            if getattr(tensor, "name", None) is None:
                tensor.name = f"{self._full_name}.{name}"
            _named_variables[tensor.name] = tensor
        return tensor

    def create_parameter(self, shape, attr=None, dtype=None,
                         is_bias: bool = False, default_initializer=None):
        """Create + initialize a Parameter (reference layers.py
        create_parameter; initializer defaults follow the reference:
        XavierUniform for weights, Constant(0) for bias)."""
        from .initializer import Constant, XavierUniform
        from ..framework.param_attr import ParamAttr
        dtype = dtypes.convert_dtype(dtype or self._dtype)
        attr = ParamAttr._to_attr(attr)
        init = (attr.initializer if attr and attr.initializer is not None
                else default_initializer)
        if init is None:
            init = Constant(0.0) if is_bias else XavierUniform()
        data = init(shape, dtype)
        p = Parameter(data, dtype=dtype,
                      name=attr.name if attr else None,
                      trainable=(attr.trainable if attr else True))
        if attr is not None:
            p.regularizer = attr.regularizer
            p.optimize_attr = {"learning_rate": attr.learning_rate}
            p.need_clip = attr.need_clip
        return p

    # -- attribute protocol -------------------------------------------------

    def __setattr__(self, name: str, value: Any):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise InvalidArgumentError(
                    "super().__init__() must be called before assigning "
                    "parameters")
            if layers is not None:
                layers.pop(name, None)
            if buffers is not None:
                buffers.pop(name, None)
            params[name] = value
            if value.name is None:
                value.name = f"{self._full_name}.{name}"
            if value.name:
                _named_variables[value.name] = value
            return
        if isinstance(value, Layer):
            if layers is None:
                raise InvalidArgumentError(
                    "super().__init__() must be called before assigning "
                    "sublayers")
            params is not None and params.pop(name, None)
            buffers is not None and buffers.pop(name, None)
            layers[name] = value
            # what a traced step calls this layer's scope (__call__)
            value.__dict__["_scope_name"] = name
            return
        if buffers is not None and name in buffers:
            if value is None or isinstance(value, Tensor):
                buffers[name] = value
                if (value is not None and name not in
                        self._non_persistable_buffer_names):
                    # keep the reassigned buffer scope-visible (the
                    # register_buffer invariant)
                    if getattr(value, "name", None) is None:
                        value.name = f"{self._full_name}.{name}"
                    _named_variables[value.name] = value
                return
        for d in (params, layers):
            if d is not None and name in d:
                del d[name]
        object.__setattr__(self, name, value)

    def __getattr__(self, name: str):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        keys = set(super().__dir__())
        keys.update(self._parameters, self._sub_layers, self._buffers)
        return sorted(keys)

    # -- iteration ----------------------------------------------------------

    def named_parameters(self, prefix: str = "",
                         include_sublayers: bool = True
                         ) -> Iterator[Tuple[str, Parameter]]:
        seen = set()
        for name, p in self._parameters.items():
            if p is not None and id(p) not in seen:
                seen.add(id(p))
                yield (prefix + ("." if prefix else "") + name), p
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                sub_prefix = prefix + ("." if prefix else "") + lname
                for n, p in layer.named_parameters(sub_prefix, True):
                    if id(p) not in seen:
                        seen.add(id(p))
                        yield n, p

    def parameters(self, include_sublayers: bool = True) -> List[Parameter]:
        return [p for _, p in self.named_parameters(
            include_sublayers=include_sublayers)]

    def named_sublayers(self, prefix: str = "", include_self: bool = False
                        ) -> Iterator[Tuple[str, "Layer"]]:
        if include_self:
            yield prefix, self
        for name, layer in self._sub_layers.items():
            if layer is None:
                continue
            sub_prefix = prefix + ("." if prefix else "") + name
            yield sub_prefix, layer
            yield from layer.named_sublayers(sub_prefix, include_self=False)

    def sublayers(self, include_self: bool = False) -> List["Layer"]:
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def children(self) -> Iterator["Layer"]:
        for _, l in self._sub_layers.items():
            if l is not None:
                yield l

    def named_children(self):
        yield from self._sub_layers.items()

    def named_buffers(self, prefix: str = "", include_sublayers: bool = True):
        for name, b in self._buffers.items():
            if b is not None:
                yield (prefix + ("." if prefix else "") + name), b
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                sub_prefix = prefix + ("." if prefix else "") + lname
                yield from layer.named_buffers(sub_prefix, True)

    def buffers(self, include_sublayers: bool = True):
        return [b for _, b in self.named_buffers(
            include_sublayers=include_sublayers)]

    # -- mode ---------------------------------------------------------------

    def train(self):
        self.training = True
        for l in self.sublayers():
            l.training = True
        return self

    def eval(self):
        self.training = False
        for l in self.sublayers():
            l.training = False
        return self

    def apply(self, fn: Callable[["Layer"], None]):
        for l in self.children():
            l.apply(fn)
        fn(self)
        return self

    # -- hooks --------------------------------------------------------------

    def register_forward_pre_hook(self, hook) -> HookRemoveHelper:
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_pre_hooks, self._hook_id)

    def register_forward_post_hook(self, hook) -> HookRemoveHelper:
        self._hook_id += 1
        self._forward_post_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_post_hooks, self._hook_id)

    # -- call ---------------------------------------------------------------

    def forward(self, *inputs, **kwargs):
        raise NotImplementedError(
            f"{type(self).__name__} must implement forward()")

    def __call__(self, *inputs, **kwargs):
        for hook in list(self._forward_pre_hooks.values()):
            out = hook(self, inputs)
            if out is not None:
                inputs = out if isinstance(out, tuple) else (out,)
        if getattr(_trace_tls, "scopes", False):
            # a step is being traced (autograd.engine.traced_scopes):
            # an op's scope becomes the layer path
            with jax.named_scope(self.__dict__.get("_scope_name")
                                 or type(self).__name__):
                outputs = self.forward(*inputs, **kwargs)
        else:
            outputs = self.forward(*inputs, **kwargs)
        for hook in list(self._forward_post_hooks.values()):
            res = hook(self, inputs, outputs)
            if res is not None:
                outputs = res
        return outputs

    # -- state dict ---------------------------------------------------------

    def state_dict(self, destination=None, include_sublayers: bool = True,
                   structured_name_prefix: str = "",
                   use_hook: bool = True) -> Dict[str, Tensor]:
        dest = destination if destination is not None else OrderedDict()
        for name, p in self._parameters.items():
            if p is not None:
                dest[structured_name_prefix + name] = p
        for name, b in self._buffers.items():
            if b is not None and name not in self._non_persistable_buffer_names:
                dest[structured_name_prefix + name] = b
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                layer.state_dict(dest, True,
                                 structured_name_prefix + lname + ".")
        return dest

    def set_state_dict(self, state_dict, use_structured_name: bool = True):
        """Load values into matching parameters/buffers (reference
        layers.py set_dict). Returns (missing_keys, unexpected_keys)."""
        own = self.state_dict()
        missing, unexpected = [], []
        matched = set()
        for key, value in state_dict.items():
            if key not in own:
                unexpected.append(key)
                continue
            target = own[key]
            arr = value.data if isinstance(value, Tensor) else np.asarray(value)
            if tuple(target.shape) != tuple(np.shape(arr)):
                raise InvalidArgumentError(
                    f"Shape mismatch for {key!r}: expected {target.shape}, "
                    f"got {list(np.shape(arr))}")
            target.set_value(value if isinstance(value, Tensor)
                             else to_tensor(arr))
            matched.add(key)
        missing = [k for k in own if k not in matched]
        return missing, unexpected

    set_dict = set_state_dict
    load_dict = set_state_dict

    # -- dtype / device movement -------------------------------------------

    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            dt = dtypes.convert_dtype(dtype)
            for p in self.parameters():
                p._data = p.data.astype(dt)
            for _, b in self.named_buffers():
                if dtypes.is_floating(b.dtype):
                    b._data = b.data.astype(dt)
            self._dtype = dt
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    # -- functional bridge (TPU-native; used by jit/pjit paths) ------------

    def functional_state(self) -> Dict[str, Any]:
        """Return {name: raw jax array} for every parameter+buffer."""
        return {k: v.data for k, v in self.state_dict().items()}

    @contextlib.contextmanager
    def load_functional_state(self, arrays: Dict[str, Any]):
        """Temporarily swap raw arrays into the layer's parameters so a jax
        transform can trace forward() against them, restoring after."""
        sd = self.state_dict()
        saved = {}
        for k, arr in arrays.items():
            if k in sd:
                saved[k] = sd[k]._data
                sd[k]._data = arr
        try:
            yield self
        finally:
            for k, old in saved.items():
                sd[k]._data = old

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, layer in self._sub_layers.items():
            mod_str = repr(layer)
            mod_str = "\n  ".join(mod_str.split("\n"))
            lines.append(f"({name}): {mod_str}")
        main = self.__class__.__name__ + "("
        if extra:
            main += extra
        if lines:
            main += "\n  " + "\n  ".join(lines) + "\n"
        return main + ")"

    def extra_repr(self) -> str:
        return ""
