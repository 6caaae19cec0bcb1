"""Optimizers: graph-building classes appending update ops.

Parity: python/paddle/fluid/optimizer.py — same classes, same accumulator
names, same minimize() contract (append_backward → regularization → clip →
per-param update ops). The update ops lower to fused XLA (ops/optimizer_ops.py)
and their ParamOut writes make the executor's donated-state write-back an
in-place TPU update.
"""
from collections import defaultdict

from .core.framework import (Variable, Parameter, build_phase,
                             default_main_program, default_startup_program,
                             program_guard)
from .core.layer_helper import LayerHelper
from .core.initializer import ConstantInitializer
from .core.backward import append_backward
from .core import unique_name
from . import regularizer as regularizer_mod

__all__ = [
    "SGD", "Momentum", "Adagrad", "Adam", "Adamax", "DecayedAdagrad",
    "Adadelta", "RMSProp", "Ftrl", "SGDOptimizer", "MomentumOptimizer",
    "AdagradOptimizer", "AdamOptimizer", "AdamaxOptimizer",
    "DecayedAdagradOptimizer", "AdadeltaOptimizer", "RMSPropOptimizer",
    "FtrlOptimizer", "ModelAverage", "Optimizer",
    "ProximalGD", "ProximalAdagrad", "ProximalGDOptimizer",
    "ProximalAdagradOptimizer", "scale_learning_rate",
    "persistable_lr_names",
]


def persistable_lr_names(program):
    """Names of the PERSISTABLE learning-rate variables the program's
    update ops read (in op order, deduped). Empty for scheduler-derived
    rates, which are recomputed in-graph each step — the single source
    of truth for both scale_learning_rate and the resilience
    Supervisor's construction-time lr_scale validation."""
    names = []
    for op in program.global_block().ops:
        for n in op.inputs.get("LearningRate", ()):
            if n and n not in names:
                v = program.global_block().vars.get(n)
                if v is not None and v.persistable:
                    names.append(n)
    return names


def scale_learning_rate(program, scope, factor):
    """Scale every persistable learning-rate variable the program's
    update ops read by `factor`, in the scope (device- or host-side
    value, dtype preserved). The resilience supervisor's rollback
    re-entry damping: after restoring a snapshot it can re-enter the
    divergent region at e.g. 0.5x LR instead of replaying the same blowup.

    Returns the list of scaled var names. Scheduler-computed rates
    (exponential_decay etc.) are re-derived in-graph from their counter
    every step, so there is no persistable to scale — if NO update op
    reads a persistable LR, this raises so the caller knows the damping
    did not take (wrap the scheduler output in a persistable var, or
    rebuild with a float learning_rate, to use lr_scale)."""
    import numpy as np
    scaled = []
    for n in persistable_lr_names(program):
        val = scope.get(n)
        if val is None:
            continue
        arr = np.asarray(val)
        scope.set(n, (arr * factor).astype(arr.dtype))
        scaled.append(n)
    if not scaled:
        raise ValueError(
            "scale_learning_rate: no persistable learning-rate variable "
            "holds a value in the scope — scheduler-derived rates are "
            "recomputed in-graph each step and cannot be damped this "
            "way")
    return scaled


class Optimizer(object):
    def __init__(self, learning_rate, regularization=None, LARS_weight_decay=0.0):
        if not isinstance(learning_rate, (float, Variable)):
            raise TypeError("learning rate should be float or Variable")
        self.regularization = regularization
        self._learning_rate = learning_rate
        self._learning_rate_map = {}
        self._accumulators = defaultdict(dict)
        self.helper = None
        self._LARS_weight_decay = LARS_weight_decay

    def _create_global_learning_rate(self):
        program = default_main_program()
        lr = self._learning_rate_map.get(program)
        if lr is not None:
            return
        if isinstance(self._learning_rate, Variable):
            self._learning_rate_map[program] = self._learning_rate
            return
        from .layers import tensor
        self._learning_rate_map[program] = tensor.create_global_var(
            name=unique_name.generate("learning_rate"),
            shape=[1], value=float(self._learning_rate),
            dtype="float32", persistable=True)

    def _global_learning_rate(self, program=None):
        if program is None:
            program = default_main_program()
        return self._learning_rate_map.get(program)

    def _create_param_lr(self, param_and_grad):
        param = param_and_grad[0]
        param_lr = param.optimize_attr.get("learning_rate", 1.0) \
            if param.optimize_attr else 1.0
        base = self._global_learning_rate()
        if param_lr == 1.0:
            return base
        return base * param_lr

    def _create_accumulators(self, block, parameters):
        pass

    def _finish_update(self, block):
        pass

    def _add_accumulator(self, name, param, dtype="float32", fill_value=0.0,
                         shape=None):
        # called in the canonical sorted-param order established by
        # _create_optimization_pass (ModelAverage's construction-time
        # sums ride all_parameters' insertion order, which is
        # deterministic per build): the unique_name counter baked into
        # the accumulator's name (and so into the program bytes, the
        # compile-cache key and the ShardingPlan walk) must not depend
        # on a caller-assembled order
        if param.name in self._accumulators[name]:
            return self._accumulators[name][param.name]
        if shape is None:
            shape = param.shape
        helper = LayerHelper(name)
        # persistable=True is load-bearing twice over: the executor's
        # donated state write-back keeps the accumulator device-resident
        # across steps, and checkpoint.CheckpointManager snapshots exactly
        # the persistable set — a non-persistable moment would silently
        # reset at every resume
        var = helper.create_global_variable(
            name=unique_name.generate(name + "_" + param.name),
            persistable=True, dtype=dtype, shape=shape)
        helper.set_variable_initializer(
            var, initializer=ConstantInitializer(value=float(fill_value)))
        self._accumulators[name][param.name] = var
        var.block.program._accumulator_owner[var.name] = param.name
        return var

    def _get_accumulator(self, name, param):
        return self._accumulators[name][param.name]

    def _append_optimize_op(self, block, param_and_grad):
        raise NotImplementedError

    def _create_optimization_pass(self, parameters_and_grads, loss,
                                  startup_program=None):
        program = loss.block.program
        # Canonical order contract (ARCHITECTURE.md §21): accumulators
        # are created — and update ops appended — in sorted-param-name
        # order, never whatever order the caller assembled. Accumulator
        # names carry unique_name counters, so the iteration order here
        # IS part of the serialized program bytes: a hash-seed- or
        # caller-order-dependent walk would re-key the persistent
        # compile cache and shuffle the ShardingPlan's shard walk on
        # every process restart. append_backward already returns pairs
        # sorted; re-sort + assert here so a hand-built list gets the
        # same guarantee.
        parameters_and_grads = sorted(parameters_and_grads,
                                      key=lambda pg: pg[0].name)
        names = [p.name for p, _ in parameters_and_grads]
        assert len(set(names)) == len(names), \
            "duplicate params break the canonical update order: %r" % names
        with program_guard(program, startup_program or
                           default_startup_program()):
            self.helper = LayerHelper(self.__class__.__name__)
            self._create_accumulators(
                loss.block, [p[0] for p in parameters_and_grads])
            self._create_global_learning_rate()

            optimize_ops = []
            for param_and_grad in parameters_and_grads:
                if param_and_grad[1] is None:
                    continue
                if param_and_grad[0].trainable:
                    op = self._append_optimize_op(loss.block, param_and_grad)
                    optimize_ops.append(op)
            self._finish_update(loss.block)
        return optimize_ops

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        from .clip import append_gradient_clip_ops
        programs = (loss.block.program,
                    startup_program or default_startup_program())
        with build_phase("minimize", *programs):
            params_grads = append_backward(loss, parameter_list, no_grad_set)
            with program_guard(*programs):
                with build_phase("clip", *programs):
                    params_grads = append_gradient_clip_ops(params_grads)
                with build_phase("regularize", *programs):
                    params_grads = regularizer_mod.append_regularization_ops(
                        params_grads, self.regularization)
            with build_phase("optimize_pass", *programs):
                optimize_ops = self._create_optimization_pass(
                    params_grads, loss, startup_program)
        return optimize_ops, params_grads


class SGDOptimizer(Optimizer):
    """Parity: sgd_op.cc."""

    def _append_optimize_op(self, block, param_and_grad):
        return block.append_op(
            type="sgd",
            inputs={"Param": [param_and_grad[0]],
                    "Grad": [param_and_grad[1]],
                    "LearningRate": [self._create_param_lr(param_and_grad)]},
            outputs={"ParamOut": [param_and_grad[0]]},
            infer_shape=False)


class MomentumOptimizer(Optimizer):
    _velocity_acc_str = "velocity"

    def __init__(self, learning_rate, momentum, use_nesterov=False, **kwargs):
        super(MomentumOptimizer, self).__init__(learning_rate, **kwargs)
        self._momentum = momentum
        self._use_nesterov = bool(use_nesterov)

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator(self._velocity_acc_str, p)

    def _append_optimize_op(self, block, param_and_grad):
        velocity_acc = self._get_accumulator(
            self._velocity_acc_str, param_and_grad[0])
        return block.append_op(
            type="momentum",
            inputs={"Param": [param_and_grad[0]],
                    "Grad": [param_and_grad[1]],
                    "Velocity": [velocity_acc],
                    "LearningRate": [self._create_param_lr(param_and_grad)]},
            outputs={"ParamOut": [param_and_grad[0]],
                     "VelocityOut": [velocity_acc]},
            attrs={"mu": self._momentum, "use_nesterov": self._use_nesterov},
            infer_shape=False)


class AdagradOptimizer(Optimizer):
    _moment_acc_str = "moment"

    def __init__(self, learning_rate, epsilon=1e-6, **kwargs):
        super(AdagradOptimizer, self).__init__(learning_rate, **kwargs)
        self._epsilon = epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator(self._moment_acc_str, p)

    def _append_optimize_op(self, block, param_and_grad):
        moment_acc = self._get_accumulator(self._moment_acc_str,
                                           param_and_grad[0])
        return block.append_op(
            type="adagrad",
            inputs={"Param": [param_and_grad[0]],
                    "Grad": [param_and_grad[1]],
                    "Moment": [moment_acc],
                    "LearningRate": [self._create_param_lr(param_and_grad)]},
            outputs={"ParamOut": [param_and_grad[0]],
                     "MomentOut": [moment_acc]},
            attrs={"epsilon": self._epsilon},
            infer_shape=False)


class AdamOptimizer(Optimizer):
    _moment1_acc_str = "moment1"
    _moment2_acc_str = "moment2"

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, **kwargs):
        super(AdamOptimizer, self).__init__(learning_rate, **kwargs)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator(self._moment1_acc_str, p)
            self._add_accumulator(self._moment2_acc_str, p)
        self._beta1_pow_acc = self._add_global_accumulator(
            "beta1_pow_acc", self._beta1)
        self._beta2_pow_acc = self._add_global_accumulator(
            "beta2_pow_acc", self._beta2)

    def _add_global_accumulator(self, name, fill_value):
        helper = LayerHelper(name)
        var = helper.create_or_get_global_variable(
            name=unique_name.generate(name), persistable=True,
            dtype="float32", shape=[1])
        helper.set_variable_initializer(
            var, initializer=ConstantInitializer(value=float(fill_value)))
        # optimizer-global state (beta pows): owner "" marks it in
        # program._accumulator_owner so the checkpoint manifest tags it as
        # optimizer state and the sharded-weight-update path never
        # pattern-matches it to some unlucky param
        var.block.program._accumulator_owner.setdefault(var.name, "")
        return var

    def _append_optimize_op(self, block, param_and_grad):
        moment1 = self._get_accumulator(self._moment1_acc_str,
                                        param_and_grad[0])
        moment2 = self._get_accumulator(self._moment2_acc_str,
                                        param_and_grad[0])
        return block.append_op(
            type="adam",
            inputs={"Param": [param_and_grad[0]],
                    "Grad": [param_and_grad[1]],
                    "LearningRate": [self._create_param_lr(param_and_grad)],
                    "Moment1": [moment1], "Moment2": [moment2],
                    "Beta1Pow": [self._beta1_pow_acc],
                    "Beta2Pow": [self._beta2_pow_acc]},
            outputs={"ParamOut": [param_and_grad[0]],
                     "Moment1Out": [moment1], "Moment2Out": [moment2]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon},
            infer_shape=False)

    def _finish_update(self, block):
        block.append_op(
            type="adam_beta_pow_update",
            inputs={"Beta1Pow": [self._beta1_pow_acc],
                    "Beta2Pow": [self._beta2_pow_acc]},
            outputs={"Beta1PowOut": [self._beta1_pow_acc],
                     "Beta2PowOut": [self._beta2_pow_acc]},
            attrs={"beta1": self._beta1, "beta2": self._beta2},
            infer_shape=False)


class AdamaxOptimizer(AdamOptimizer):
    _moment_acc_str = "moment"
    _inf_norm_acc_str = "inf_norm"

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator(self._moment_acc_str, p)
            self._add_accumulator(self._inf_norm_acc_str, p)
        self._beta1_pow_acc = self._add_global_accumulator(
            "beta1_pow_acc", self._beta1)

    def _append_optimize_op(self, block, param_and_grad):
        moment = self._get_accumulator(self._moment_acc_str, param_and_grad[0])
        inf_norm = self._get_accumulator(self._inf_norm_acc_str,
                                         param_and_grad[0])
        return block.append_op(
            type="adamax",
            inputs={"Param": [param_and_grad[0]],
                    "Grad": [param_and_grad[1]],
                    "LearningRate": [self._create_param_lr(param_and_grad)],
                    "Moment": [moment], "InfNorm": [inf_norm],
                    "Beta1Pow": [self._beta1_pow_acc]},
            outputs={"ParamOut": [param_and_grad[0]],
                     "MomentOut": [moment], "InfNormOut": [inf_norm]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon},
            infer_shape=False)

    def _finish_update(self, block):
        block.append_op(
            type="scale",
            inputs={"X": [self._beta1_pow_acc]},
            outputs={"Out": [self._beta1_pow_acc]},
            attrs={"scale": self._beta1},
            infer_shape=False)


class DecayedAdagradOptimizer(Optimizer):
    _moment_acc_str = "moment"

    def __init__(self, learning_rate, decay=0.95, epsilon=1e-6, **kwargs):
        super(DecayedAdagradOptimizer, self).__init__(learning_rate, **kwargs)
        self._decay = decay
        self._epsilon = epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator(self._moment_acc_str, p)

    def _append_optimize_op(self, block, param_and_grad):
        moment = self._get_accumulator(self._moment_acc_str, param_and_grad[0])
        return block.append_op(
            type="decayed_adagrad",
            inputs={"Param": [param_and_grad[0]],
                    "Grad": [param_and_grad[1]],
                    "Moment": [moment],
                    "LearningRate": [self._create_param_lr(param_and_grad)]},
            outputs={"ParamOut": [param_and_grad[0]], "MomentOut": [moment]},
            attrs={"decay": self._decay, "epsilon": self._epsilon},
            infer_shape=False)


class AdadeltaOptimizer(Optimizer):
    _avg_squared_grad_acc_str = "_avg_squared_grad"
    _avg_squared_update_acc_str = "_avg_squared_update"

    def __init__(self, learning_rate, epsilon=1e-6, rho=0.95, **kwargs):
        super(AdadeltaOptimizer, self).__init__(learning_rate, **kwargs)
        self._epsilon = epsilon
        self._rho = rho

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator(self._avg_squared_grad_acc_str, p)
            self._add_accumulator(self._avg_squared_update_acc_str, p)

    def _append_optimize_op(self, block, param_and_grad):
        g = self._get_accumulator(self._avg_squared_grad_acc_str,
                                  param_and_grad[0])
        u = self._get_accumulator(self._avg_squared_update_acc_str,
                                  param_and_grad[0])
        return block.append_op(
            type="adadelta",
            inputs={"Param": [param_and_grad[0]],
                    "Grad": [param_and_grad[1]],
                    "AvgSquaredGrad": [g], "AvgSquaredUpdate": [u]},
            outputs={"ParamOut": [param_and_grad[0]],
                     "AvgSquaredGradOut": [g], "AvgSquaredUpdateOut": [u]},
            attrs={"epsilon": self._epsilon, "rho": self._rho},
            infer_shape=False)


class RMSPropOptimizer(Optimizer):
    _momentum_acc_str = "momentum"
    _mean_square_acc_str = "mean_square"

    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 **kwargs):
        super(RMSPropOptimizer, self).__init__(learning_rate, **kwargs)
        self._rho = rho
        self._epsilon = epsilon
        self._momentum = momentum

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator(self._momentum_acc_str, p)
            self._add_accumulator(self._mean_square_acc_str, p)

    def _append_optimize_op(self, block, param_and_grad):
        momentum_acc = self._get_accumulator(self._momentum_acc_str,
                                             param_and_grad[0])
        mean_square_acc = self._get_accumulator(self._mean_square_acc_str,
                                                param_and_grad[0])
        return block.append_op(
            type="rmsprop",
            inputs={"Param": [param_and_grad[0]],
                    "Grad": [param_and_grad[1]],
                    "Moment": [momentum_acc],
                    "MeanSquare": [mean_square_acc],
                    "LearningRate": [self._create_param_lr(param_and_grad)]},
            outputs={"ParamOut": [param_and_grad[0]],
                     "MomentOut": [momentum_acc],
                     "MeanSquareOut": [mean_square_acc]},
            attrs={"epsilon": self._epsilon, "decay": self._rho,
                   "momentum": self._momentum},
            infer_shape=False)


class FtrlOptimizer(Optimizer):
    _squared_acc_str = "squared"
    _linear_acc_str = "linear"

    def __init__(self, learning_rate, l1=0.0, l2=0.0, lr_power=-0.5, **kwargs):
        super(FtrlOptimizer, self).__init__(learning_rate, **kwargs)
        self._l1 = l1
        self._l2 = l2
        self._lr_power = lr_power

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator(self._squared_acc_str, p)
            self._add_accumulator(self._linear_acc_str, p)

    def _append_optimize_op(self, block, param_and_grad):
        sq = self._get_accumulator(self._squared_acc_str, param_and_grad[0])
        lin = self._get_accumulator(self._linear_acc_str, param_and_grad[0])
        return block.append_op(
            type="ftrl",
            inputs={"Param": [param_and_grad[0]],
                    "Grad": [param_and_grad[1]],
                    "SquaredAccumulator": [sq],
                    "LinearAccumulator": [lin],
                    "LearningRate": [self._create_param_lr(param_and_grad)]},
            outputs={"ParamOut": [param_and_grad[0]],
                     "SquaredAccumOut": [sq], "LinearAccumOut": [lin]},
            attrs={"l1": self._l1, "l2": self._l2, "lr_power": self._lr_power},
            infer_shape=False)


class ProximalGDOptimizer(Optimizer):
    """Parity: proximal_gd_op.cc (FOBOS; the reference registers the op
    without an era Python class): prox = param - lr * grad;
    param = sign(prox) / (1 + lr*l2) * max(|prox| - lr*l1, 0)."""

    def __init__(self, learning_rate, l1=0.0, l2=0.0, **kwargs):
        super(ProximalGDOptimizer, self).__init__(learning_rate, **kwargs)
        self._l1 = l1
        self._l2 = l2

    def _append_optimize_op(self, block, param_and_grad):
        return block.append_op(
            type="proximal_gd",
            inputs={"Param": [param_and_grad[0]],
                    "Grad": [param_and_grad[1]],
                    "LearningRate": [self._create_param_lr(param_and_grad)]},
            outputs={"ParamOut": [param_and_grad[0]]},
            attrs={"l1": self._l1, "l2": self._l2},
            infer_shape=False)


class ProximalAdagradOptimizer(Optimizer):
    """Parity: proximal_adagrad_op.cc — adagrad-scaled proximal step."""

    _moment_acc_str = "moment"

    def __init__(self, learning_rate, l1=0.0, l2=0.0, **kwargs):
        super(ProximalAdagradOptimizer, self).__init__(learning_rate,
                                                       **kwargs)
        self._l1 = l1
        self._l2 = l2

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator(self._moment_acc_str, p)

    def _append_optimize_op(self, block, param_and_grad):
        moment = self._get_accumulator(self._moment_acc_str,
                                       param_and_grad[0])
        return block.append_op(
            type="proximal_adagrad",
            inputs={"Param": [param_and_grad[0]],
                    "Grad": [param_and_grad[1]],
                    "Moment": [moment],
                    "LearningRate": [self._create_param_lr(param_and_grad)]},
            outputs={"ParamOut": [param_and_grad[0]],
                     "MomentOut": [moment]},
            attrs={"l1": self._l1, "l2": self._l2},
            infer_shape=False)


class ModelAverage(Optimizer):
    """Parity: fluid.optimizer.ModelAverage (average_accumulates_op).

    Maintains running parameter sums; `apply()` swaps averaged params in,
    `restore()` swaps them back.
    """

    def __init__(self, average_window_rate, min_average_window=10000,
                 max_average_window=10000, **kwargs):
        super(ModelAverage, self).__init__(0.0, **kwargs)
        self.average_window = average_window_rate
        self.min_average_window = min_average_window
        self.max_average_window = max_average_window
        self.params_grads = []
        self._sums = {}
        self._num_updates = {}
        program = default_main_program()
        for param in program.global_block().all_parameters():
            if param.do_model_average is False:
                continue
            s = self._add_accumulator("sum_1", param)
            self._sums[param.name] = s
            program.current_block().append_op(
                type="elementwise_add",
                inputs={"X": [s], "Y": [param]},
                outputs={"Out": [s]},
                attrs={"axis": -1},
                infer_shape=False)
        self._counter = self._add_counter()

    def _add_counter(self):
        helper = LayerHelper("ma_counter")
        var = helper.create_or_get_global_variable(
            name=unique_name.generate("ma_counter"), persistable=True,
            dtype="float32", shape=[1])
        helper.set_variable_initializer(var, ConstantInitializer(0.0))
        var.block.program._accumulator_owner.setdefault(var.name, "")
        default_main_program().current_block().append_op(
            type="increment", inputs={"X": [var]}, outputs={"Out": [var]},
            attrs={"step": 1.0}, infer_shape=False)
        return var

    def apply(self, executor, need_restore=True):
        import contextlib

        @contextlib.contextmanager
        def _ctx():
            from .core.executor import global_scope
            import numpy as np
            scope = global_scope()
            backup = {}
            counter = float(np.asarray(scope.get(self._counter.name))[0])
            counter = max(counter, 1.0)
            for pname, svar in self._sums.items():
                backup[pname] = scope.get(pname)
                s = np.asarray(scope.get(svar.name))
                scope.set(pname, (s / counter).astype(s.dtype))
            yield
            if need_restore:
                for pname, val in backup.items():
                    scope.set(pname, val)
        return _ctx()

    def restore(self, executor):
        pass


# short aliases (parity: fluid exposes both)
SGD = SGDOptimizer
Momentum = MomentumOptimizer
Adagrad = AdagradOptimizer
Adam = AdamOptimizer
Adamax = AdamaxOptimizer
DecayedAdagrad = DecayedAdagradOptimizer
Adadelta = AdadeltaOptimizer
RMSProp = RMSPropOptimizer
Ftrl = FtrlOptimizer
ProximalGD = ProximalGDOptimizer
ProximalAdagrad = ProximalAdagradOptimizer


# the op types an optimizer's `_append_optimize_op` appends, the update
# itself: what device time by fluid op reads as "the optimizer"
# (benchmark/layer_metrics/optimizer_ms_per_step.py). The generic `scale`,
# `increment` and `elementwise_*` ops of the schedule, of clipping and of
# regularization are other ops' types too and are not in it.
UPDATE_OP_TYPES = ("sgd", "momentum", "adagrad", "adam",
                   "adam_beta_pow_update", "adamax", "decayed_adagrad",
                   "adadelta", "rmsprop", "ftrl", "proximal_gd",
                   "proximal_adagrad")
