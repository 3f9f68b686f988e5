"""Optimizers.

Analog of /root/reference/paddle/fluid/operators/optimizers/ (sgd/momentum/
adam/adamw/lamb/... CUDA kernels) + python/paddle/optimizer/. Each optimizer
defines one pure ``_update(param, grad, slots, lr, **hyper) -> (new_param,
new_slots)`` rule in jnp; the eager ``step()`` applies it per parameter
(each application is one fused XLA kernel — the hand-written CUDA optimizer
kernel analog), and the compiled training path applies the same rule inside
jit via ``functional_update`` so eager/compiled parity is exact.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..autograd import engine
from ..core import dtype as dtypes
from ..core.errors import InvalidArgumentError
from ..core.tensor import Parameter, Tensor, to_tensor
from .lr import LRScheduler

__all__ = ["Optimizer", "SGD", "Momentum", "Adagrad", "Adam", "AdamW",
           "Adamax", "AdaDelta", "RMSProp", "Lamb", "Lars"]


class Optimizer:
    """Base optimizer (reference python/paddle/optimizer/optimizer.py).

    Slot variables (moments etc.) mirror the reference's accumulator
    protocol; ``state_dict``/``set_state_dict`` round-trip them plus the LR
    scheduler state.
    """

    _slot_names: Tuple[str, ...] = ()

    def __init__(self, learning_rate=0.001, parameters=None,
                 weight_decay=None, grad_clip=None, name=None,
                 multi_precision=False):
        if parameters is not None:
            parameters = list(parameters)
            if parameters and isinstance(parameters[0], dict):
                # param groups: flatten, remember per-group lr scale
                flat = []
                for group in parameters:
                    for p in group["params"]:
                        if "learning_rate" in group:
                            p.optimize_attr["learning_rate"] = \
                                group["learning_rate"]
                        if "weight_decay" in group:
                            p.optimize_attr["weight_decay"] = \
                                group["weight_decay"]
                        flat.append(p)
                parameters = flat
        self._parameter_list = parameters
        self._learning_rate = learning_rate
        self._grad_clip = grad_clip
        if weight_decay is None:
            self._weight_decay = 0.0
            self._wd_is_l2 = True
        elif isinstance(weight_decay, (int, float)):
            self._weight_decay = float(weight_decay)
            self._wd_is_l2 = True
        else:
            # L2Decay/L1Decay object from paddle1_tpu.regularizer
            self._weight_decay = float(getattr(weight_decay, "coeff",
                                               getattr(weight_decay,
                                                       "_coeff", 0.0)))
            self._wd_is_l2 = type(weight_decay).__name__ != "L1Decay"
        self._slots: Dict[int, Dict[str, jax.Array]] = {}
        self._step_count = 0
        self._accumulators_built = False
        self._current_param_name = None

    # -- learning rate ------------------------------------------------------

    def get_lr(self) -> float:
        if isinstance(self._learning_rate, LRScheduler):
            return float(self._learning_rate())
        return float(self._learning_rate)

    def set_lr(self, value: float):
        if isinstance(self._learning_rate, LRScheduler):
            raise InvalidArgumentError(
                "Cannot set_lr when using an LRScheduler")
        self._learning_rate = float(value)

    # -- slots --------------------------------------------------------------

    def _init_slots(self, p: Parameter) -> Dict[str, jax.Array]:
        """Default: one zero buffer per slot name, param-shaped."""
        return {name: jnp.zeros_like(p.data) for name in self._slot_names}

    def _get_slots(self, p: Parameter) -> Dict[str, jax.Array]:
        s = self._slots.get(id(p))
        if s is None:
            s = self._init_slots(p)
            self._slots[id(p)] = s
        return s

    # -- the update rule (override per optimizer) ---------------------------

    def _update(self, param, grad, slots, lr, step):
        raise NotImplementedError

    def _update_sparse(self, param, grad, slots, lr, step):
        """Row-sparse update for an IndexedSlices grad (rows pre-merged).
        Return (new_param, new_slots), or None to densify instead —
        the reference's SelectedRows optimizer-kernel dispatch
        (adam_op.h SparseAdamFunctor, sgd_op.h SelectedRows branch)."""
        return None

    # -- eager step ---------------------------------------------------------

    @engine.no_grad()
    def step(self):
        params = self._parameter_list
        if params is None:
            raise InvalidArgumentError(
                "Optimizer constructed without parameters: pass parameters= "
                "in eager mode (reference optimizer.py behavior)")
        params_grads = [(p, p.grad) for p in params
                        if not p.stop_gradient and p.grad is not None]
        if self._grad_clip is not None:
            params_grads = self._grad_clip(params_grads)
        self._step_count += 1
        lr = self.get_lr()
        from ..core.indexed_slices import IndexedSlices
        for p, g in params_grads:
            self._current_param_name = p.name
            lr_p = lr * p.optimize_attr.get("learning_rate", 1.0)
            garr = g.data.astype(p.data.dtype) if g.data.dtype != p.data.dtype \
                else g.data
            slots = self._get_slots(p)
            if isinstance(garr, IndexedSlices):
                # row-sparse grad (SelectedRows analog): regularizers are
                # skipped, matching the reference's warning-and-skip on
                # SelectedRows grads (regularizer.py append_regularization)
                merged = garr.merge()
                res = self._update_sparse(p.data, merged, slots, lr_p,
                                          self._step_count)
                if res is None:
                    res = self._update(p.data, merged.to_dense(), slots,
                                       lr_p, self._step_count)
                new_param, new_slots = res
            else:
                # per-parameter L2 regularizer (reference regularizer-as-op)
                if getattr(p, "regularizer", None) is not None:
                    garr = garr + float(getattr(p.regularizer, "coeff",
                                                0.0)) * p.data
                new_param, new_slots = self._update(p.data, garr, slots,
                                                    lr_p, self._step_count)
            p._data = new_param
            self._slots[id(p)] = new_slots

    minimize_step = step

    def clear_grad(self, set_to_zero: bool = False):
        if self._parameter_list is not None:
            for p in self._parameter_list:
                p.clear_grad()

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        """backward + step (reference Optimizer.minimize)."""
        loss.backward()
        self.step()
        return None, [(p, p.grad) for p in (self._parameter_list or [])]

    # -- functional path (used by jit/pjit training steps) ------------------

    def functional_init(self, params: Dict[str, jax.Array]):
        return {k: {name: jnp.zeros_like(v) for name in self._slot_names}
                for k, v in params.items()}, jnp.zeros((), jnp.int32)

    def functional_update(self, params, grads, opt_state, lr):
        """Pure: (params, grads, (slots, step), lr) -> (new_params,
        new_state). Traceable under jit/pjit; identical math to step()."""
        slots, step = opt_state
        step = step + 1
        new_params, new_slots = {}, {}
        for k, p in params.items():
            g = grads[k].astype(p.dtype)
            np_, ns = self._update(p, g, slots[k], lr, step)
            new_params[k] = np_
            new_slots[k] = ns
        return new_params, (new_slots, step)

    # -- state dict ---------------------------------------------------------

    def state_dict(self):
        out = {"step": self._step_count}
        if self._parameter_list is not None:
            for p in self._parameter_list:
                s = self._slots.get(id(p))
                if s:
                    for name, arr in s.items():
                        out[f"{p.name}__{name}"] = to_tensor(arr)
        if isinstance(self._learning_rate, LRScheduler):
            out["LR_Scheduler"] = self._learning_rate.state_dict()
        return out

    def set_state_dict(self, state):
        self._step_count = int(state.get("step", 0))
        if isinstance(self._learning_rate, LRScheduler) and \
                "LR_Scheduler" in state:
            self._learning_rate.set_state_dict(state["LR_Scheduler"])
        if self._parameter_list is not None:
            for p in self._parameter_list:
                slots = {}
                for name in self._slot_names:
                    key = f"{p.name}__{name}"
                    if key in state:
                        v = state[key]
                        slots[name] = v.data if isinstance(v, Tensor) \
                            else jnp.asarray(np.asarray(v))
                if slots:
                    self._slots[id(p)] = slots

    # decoupled-vs-L2 weight decay helper
    def _l2(self, grad, param):
        if self._weight_decay and self._wd_is_l2:
            return grad + self._weight_decay * param
        return grad


class SGD(Optimizer):
    def _update(self, param, grad, slots, lr, step):
        grad = self._l2(grad, param)
        return param - lr * grad, slots

    def _update_sparse(self, param, grad, slots, lr, step):
        # touched rows only (reference sgd_op.h SelectedRows branch)
        return param.at[grad.rows].add(
            (-lr * grad.values).astype(param.dtype)), slots


class Momentum(Optimizer):
    _slot_names = ("velocity",)

    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _update(self, param, grad, slots, lr, step):
        grad = self._l2(grad, param)
        v = self._momentum * slots["velocity"] + grad
        if self._nesterov:
            new_p = param - lr * (grad + self._momentum * v)
        else:
            new_p = param - lr * v
        return new_p, {"velocity": v}


class Lars(Momentum):
    """LARS (reference lars_momentum_op.cc): layer-wise adaptive rate."""

    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 lars_coeff=0.001, lars_weight_decay=0.0005, epsilon=1e-9,
                 weight_decay=None, grad_clip=None,
                 exclude_from_weight_decay=None, name=None):
        super().__init__(learning_rate, momentum, parameters,
                         weight_decay=weight_decay, grad_clip=grad_clip)
        self._lars_coeff = lars_coeff
        self._lars_wd = lars_weight_decay
        self._epsilon = epsilon

    def _update(self, param, grad, slots, lr, step):
        # user regularization applies BEFORE the LARS math (reference
        # LarsMomentumOptimizer: regularization ops precede the op,
        # which then adds its own lars_weight_decay term)
        grad = self._l2(grad, param)
        p_norm = jnp.sqrt(jnp.sum(jnp.square(param)))
        g_norm = jnp.sqrt(jnp.sum(jnp.square(grad)))
        # lars_momentum_op.h: the adaptive rate applies only when
        # weight decay is on AND both norms are positive; otherwise the
        # update degrades to plain momentum at the base lr
        adaptive = (self._lars_wd > 0)
        local_lr = jnp.where(
            adaptive & (p_norm > 0) & (g_norm > 0),
            self._lars_coeff * p_norm /
            (g_norm + self._lars_wd * p_norm + self._epsilon),
            1.0)
        v = self._momentum * slots["velocity"] + lr * local_lr * (
            grad + self._lars_wd * param)
        return param - v, {"velocity": v}


class Ftrl(Optimizer):
    """FTRL-proximal (reference ftrl_op.h): per-coordinate adaptive
    rates from the squared-gradient accumulator, L1 shrinkage through
    the linear accumulator. The reference kernel adds 1e-10 to both
    regularizers; kept for bit-parity."""

    _slot_names = ("squared", "linear")

    def __init__(self, learning_rate=0.001, l1=0.0, l2=0.0,
                 lr_power=-0.5, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay,
                         grad_clip)
        self._ftrl_l1 = float(l1) + 1e-10
        self._ftrl_l2 = float(l2) + 1e-10
        self._lr_power = float(lr_power)

    def _update(self, param, grad, slots, lr, step):
        grad = self._l2(grad, param)
        l1, l2 = self._ftrl_l1, self._ftrl_l2
        sq, lin = slots["squared"], slots["linear"]
        new_sq = sq + grad * grad
        p = self._lr_power
        if p == -0.5:
            sigma = (jnp.sqrt(new_sq) - jnp.sqrt(sq)) / lr
            y = jnp.sqrt(new_sq) / lr + 2.0 * l2
        else:
            sigma = (new_sq ** (-p) - sq ** (-p)) / lr
            y = new_sq ** (-p) / lr + 2.0 * l2
        new_lin = lin + grad - sigma * param
        x = l1 * jnp.sign(new_lin) - new_lin
        new_p = jnp.where(jnp.abs(new_lin) > l1, x / y, 0.0)
        return new_p, {"squared": new_sq, "linear": new_lin}


class Adagrad(Optimizer):
    _slot_names = ("moment",)

    def __init__(self, learning_rate=0.001, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None,
                 initial_accumulator_value=0.0, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value

    def _init_slots(self, p):
        return {"moment": jnp.full_like(p.data, self._init_acc)}

    def _update(self, param, grad, slots, lr, step):
        grad = self._l2(grad, param)
        m = slots["moment"] + grad * grad
        return param - lr * grad / (jnp.sqrt(m) + self._epsilon), \
            {"moment": m}


class Adam(Optimizer):
    """Adam (reference adam_op.cu). Bias-corrected, f32 moments even for
    bf16 params (multi-precision semantics by default on TPU)."""

    _slot_names = ("moment1", "moment2")

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        self._lazy_mode = lazy_mode

    def _init_slots(self, p):
        f32 = jnp.float32
        return {name: jnp.zeros(p.data.shape, f32)
                for name in self._slot_names}

    def _decoupled_decay(self, param, lr):
        return 0.0

    def _update(self, param, grad, slots, lr, step):
        g = self._l2(grad.astype(jnp.float32), param.astype(jnp.float32))
        m1 = self._beta1 * slots["moment1"] + (1 - self._beta1) * g
        m2 = self._beta2 * slots["moment2"] + (1 - self._beta2) * g * g
        bc1 = 1 - self._beta1 ** step
        bc2 = 1 - self._beta2 ** step
        update = (m1 / bc1) / (jnp.sqrt(m2 / bc2) + self._epsilon)
        pf = param.astype(jnp.float32)
        pf = pf - lr * update - lr * self._decoupled_decay(pf, lr)
        return pf.astype(param.dtype), {"moment1": m1, "moment2": m2}

    def _update_sparse(self, param, grad, slots, lr, step):
        """lazy_mode=True: moments/params touched rows only (reference
        SparseAdamFunctor with lazy_mode, adam_op.h:473). Default mode
        decays every row's moments (grad=0 rows included), which is the
        densified update — handled by the base-class fallback."""
        if not self._lazy_mode:
            return None
        rows = grad.rows
        g = grad.values.astype(jnp.float32)
        m1r = slots["moment1"][rows]
        m2r = slots["moment2"][rows]
        m1r = self._beta1 * m1r + (1 - self._beta1) * g
        m2r = self._beta2 * m2r + (1 - self._beta2) * g * g
        bc1 = 1 - self._beta1 ** step
        bc2 = 1 - self._beta2 ** step
        update = (m1r / bc1) / (jnp.sqrt(m2r / bc2) + self._epsilon)
        pr = param[rows].astype(jnp.float32)
        pr = pr - lr * update - lr * self._decoupled_decay(pr, lr)
        return (param.at[rows].set(pr.astype(param.dtype)),
                {"moment1": slots["moment1"].at[rows].set(m1r),
                 "moment2": slots["moment2"].at[rows].set(m2r)})


class AdamW(Adam):
    """Decoupled weight decay (reference adamw: scales param by
    (1 - lr*coeff) before the adam update)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 grad_clip=None, lr_ratio=None, apply_decay_param_fun=None,
                 multi_precision=False, lazy_mode=False, name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         None, grad_clip, lazy_mode=lazy_mode)
        self._coeff = float(weight_decay) if not hasattr(
            weight_decay, "coeff") else weight_decay.coeff
        self._apply_decay_fn = apply_decay_param_fun
        self._current_param_name = None

    def _update(self, param, grad, slots, lr, step):
        decay = self._coeff
        if self._apply_decay_fn is not None and \
                self._current_param_name is not None and \
                not self._apply_decay_fn(self._current_param_name):
            decay = 0.0
        g = grad.astype(jnp.float32)
        m1 = self._beta1 * slots["moment1"] + (1 - self._beta1) * g
        m2 = self._beta2 * slots["moment2"] + (1 - self._beta2) * g * g
        bc1 = 1 - self._beta1 ** step
        bc2 = 1 - self._beta2 ** step
        update = (m1 / bc1) / (jnp.sqrt(m2 / bc2) + self._epsilon)
        pf = param.astype(jnp.float32) * (1 - lr * decay)
        pf = pf - lr * update
        return pf.astype(param.dtype), {"moment1": m1, "moment2": m2}

    def _decoupled_decay(self, param, lr):
        # used by the inherited lazy sparse path (_update_sparse)
        decay = self._coeff
        if self._apply_decay_fn is not None and \
                self._current_param_name is not None and \
                not self._apply_decay_fn(self._current_param_name):
            decay = 0.0
        return decay * param


class Adamax(Optimizer):
    _slot_names = ("moment", "inf_norm")

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _update(self, param, grad, slots, lr, step):
        g = self._l2(grad, param)
        m = self._beta1 * slots["moment"] + (1 - self._beta1) * g
        u = jnp.maximum(self._beta2 * slots["inf_norm"], jnp.abs(g))
        lr_t = lr / (1 - self._beta1 ** step)
        return param - lr_t * m / (u + self._epsilon), \
            {"moment": m, "inf_norm": u}


class AdaDelta(Optimizer):
    _slot_names = ("avg_squared_grad", "avg_squared_update")

    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._rho, self._epsilon = rho, epsilon

    def _update(self, param, grad, slots, lr, step):
        g = self._l2(grad, param)
        asg = self._rho * slots["avg_squared_grad"] + (1 - self._rho) * g * g
        upd = g * jnp.sqrt(slots["avg_squared_update"] + self._epsilon) / \
            jnp.sqrt(asg + self._epsilon)
        asu = self._rho * slots["avg_squared_update"] + \
            (1 - self._rho) * upd * upd
        return param - lr * upd, {"avg_squared_grad": asg,
                                  "avg_squared_update": asu}


class RMSProp(Optimizer):
    _slot_names = ("mean_square", "mean_grad", "momentum")

    def __init__(self, learning_rate=0.01, rho=0.95, epsilon=1e-6,
                 momentum=0.0, centered=False, parameters=None,
                 weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._rho = rho
        self._epsilon = epsilon
        self._momentum = momentum
        self._centered = centered

    def _update(self, param, grad, slots, lr, step):
        g = self._l2(grad, param)
        ms = self._rho * slots["mean_square"] + (1 - self._rho) * g * g
        if self._centered:
            mg = self._rho * slots["mean_grad"] + (1 - self._rho) * g
            denom = jnp.sqrt(ms - mg * mg + self._epsilon)
        else:
            mg = slots["mean_grad"]
            denom = jnp.sqrt(ms + self._epsilon)
        mom = self._momentum * slots["momentum"] + lr * g / denom
        return param - mom, {"mean_square": ms, "mean_grad": mg,
                             "momentum": mom}


class Lamb(Optimizer):
    """LAMB (reference lamb_op.cc): Adam update rescaled by trust ratio."""

    _slot_names = ("moment1", "moment2")

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01,
                 beta1=0.9, beta2=0.999, epsilon=1e-6, parameters=None,
                 grad_clip=None, exclude_from_weight_decay_fn=None,
                 name=None):
        super().__init__(learning_rate, parameters, None, grad_clip)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._lamb_wd = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn

    def _init_slots(self, p):
        return {name: jnp.zeros(p.data.shape, jnp.float32)
                for name in self._slot_names}

    def _update(self, param, grad, slots, lr, step):
        g = grad.astype(jnp.float32)
        pf = param.astype(jnp.float32)
        m1 = self._beta1 * slots["moment1"] + (1 - self._beta1) * g
        m2 = self._beta2 * slots["moment2"] + (1 - self._beta2) * g * g
        bc1 = 1 - self._beta1 ** step
        bc2 = 1 - self._beta2 ** step
        r = (m1 / bc1) / (jnp.sqrt(m2 / bc2) + self._epsilon) + \
            self._lamb_wd * pf
        p_norm = jnp.sqrt(jnp.sum(pf * pf))
        r_norm = jnp.sqrt(jnp.sum(r * r))
        trust = jnp.where((p_norm > 0) & (r_norm > 0), p_norm / r_norm, 1.0)
        pf = pf - lr * trust * r
        return pf.astype(param.dtype), {"moment1": m1, "moment2": m2}
