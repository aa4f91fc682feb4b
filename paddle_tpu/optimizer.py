"""Optimizer classes emitting optimizer ops + accumulators.

Parity with reference ``python/paddle/v2/fluid/optimizer.py`` (SGD/Momentum/
Adagrad/Adam/Adamax/DecayedAdagrad + global_step/minimize) and the legacy
``FirstOrderOptimizer.h`` family (AdaDelta, RMSProp, Ftrl added). The emitted
update ops join fwd/bwd in the same block, so Executor.run does
forward+backward+update as ONE donated XLA computation — the TPU answer to
the reference's separate updater stage (``TrainerInternal.cpp:66-171``).
"""

import numpy as np

from .core import unique_name
from .core.framework import default_main_program, default_startup_program
from .core.backward import append_backward
from .initializer import ConstantInitializer
from .regularizer import append_regularization_ops

__all__ = ["SGD", "Momentum", "Adagrad", "Adam", "Adamax", "DecayedAdagrad",
           "AdaDelta", "RMSProp", "Ftrl", "SGDOptimizer",
           "MomentumOptimizer", "AdagradOptimizer", "AdamOptimizer",
           "AdamaxOptimizer", "DecayedAdagradOptimizer",
           "AdaDeltaOptimizer", "RMSPropOptimizer", "FtrlOptimizer",
           "Optimizer", "ModelAverage"]


class Optimizer:
    def __init__(self, learning_rate=0.001, regularization=None,
                 global_step=None):
        self._learning_rate = learning_rate
        self.regularization = regularization
        self._global_step = global_step
        self._lr_var = None
        self._accumulators = {}

    # -- plumbing ------------------------------------------------------------
    def _get_main(self, loss):
        return loss.block.program

    def _create_lr_var(self, main, startup):
        if self._lr_var is not None:
            return self._lr_var
        if not isinstance(self._learning_rate, (int, float)):
            # a Variable (e.g. produced by a lr-schedule subgraph)
            self._lr_var = self._learning_rate
            return self._lr_var
        name = unique_name.generate("learning_rate")
        block = main.global_block()
        var = block.create_var(name=name, shape=[1], dtype="float32",
                               persistable=True, stop_gradient=True)
        svar = startup.global_block().create_var(
            name=name, shape=[1], dtype="float32", persistable=True)
        ConstantInitializer(float(self._learning_rate))(
            svar, startup.global_block())
        self._lr_var = var
        return var

    def _lr_for_param(self, main, param):
        mult = param.optimize_attr.get("learning_rate", 1.0)
        if mult == 1.0:
            return self._lr_var
        block = main.global_block()
        out = block.create_var(
            name=unique_name.generate("%s.lr" % param.name), shape=[1],
            dtype="float32", stop_gradient=True)
        block.append_op("scale", inputs={"X": [self._lr_var.name]},
                        outputs={"Out": [out.name]},
                        attrs={"scale": float(mult)})
        return out

    def _add_accumulator(self, name, param, main, startup, fill_value=0.0,
                         shape=None):
        key = (name, param.name)
        if key in self._accumulators:
            return self._accumulators[key]
        shape = list(shape if shape is not None else param.shape)
        vname = unique_name.generate("%s_%s_acc" % (param.name, name))
        block = main.global_block()
        # A distributed embedding table's row-shaped slots (Adam
        # moments etc.) are registered alongside it, so DistStrategy
        # row-shards them by the same rule and checkpoint reshard
        # re-permutes them with the table. Scalar slots (beta powers,
        # shape [1]) stay replicated.
        tables = getattr(main, "_dist_embeddings", None)
        if tables is not None and param.name in tables and \
                tables[param.name].get("slot_of") is None and \
                shape and shape[0] == tables[param.name]["padded"]:
            info = tables[param.name]
            tables[vname] = {"vocab": info["vocab"],
                             "padded": info["padded"],
                             "dim": info["dim"], "slot_of": param.name}
        var = block.create_var(name=vname, shape=shape, dtype=param.dtype,
                               persistable=True, stop_gradient=True)
        svar = startup.global_block().create_var(
            name=vname, shape=shape, dtype=param.dtype, persistable=True)
        ConstantInitializer(fill_value)(svar, startup.global_block())
        self._accumulators[key] = var
        return var

    # -- public --------------------------------------------------------------
    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        main = self._get_main(loss)
        startup = startup_program or default_startup_program()
        # the roles the executor's compiled steps go by (Program.name)
        if main.name is None:
            main.name = "train"
        if startup.name is None:
            startup.name = "startup"
        params_grads = append_backward(loss, parameter_list, no_grad_set)
        params_grads = append_gradient_clip_ops(params_grads)
        params_grads = append_regularization_ops(params_grads,
                                                 self.regularization)
        optimize_ops = self._create_optimization_pass(params_grads, loss,
                                                      main, startup)
        if self._global_step is not None:
            loss.block.append_op(
                "increment", inputs={"X": [self._global_step.name]},
                outputs={"Out": [self._global_step.name]},
                attrs={"step": 1.0}, infer_shape=False)
        return optimize_ops, params_grads

    def _create_optimization_pass(self, params_grads, loss, main, startup):
        self._create_lr_var(main, startup)
        ops = []
        for param, grad in params_grads:
            if grad is None:
                continue
            self._check_sparse(grad)
            ops.append(self._append_optimize_op(main, startup, param, grad))
        return ops

    def _append_optimize_op(self, main, startup, param, grad):
        raise NotImplementedError

    @staticmethod
    def _grad_inputs(grad):
        """Grad input slots; sparse (SelectedRows-style) grads add Rows."""
        ins = {"Grad": [grad.name]}
        rows = getattr(grad, "selected_rows", None)
        if rows is not None:
            ins["Rows"] = [rows.name]
        return ins

    _SPARSE_CAPABLE = False

    def _check_sparse(self, grad):
        if getattr(grad, "selected_rows", None) is not None and \
                not self._SPARSE_CAPABLE:
            raise NotImplementedError(
                "%s has no sparse (SelectedRows) update rule — use "
                "SGD/Momentum/Adagrad/Adam for is_sparse embeddings"
                % type(self).__name__)


class SGD(Optimizer):
    _SPARSE_CAPABLE = True

    def _append_optimize_op(self, main, startup, param, grad):
        lr = self._lr_for_param(main, param)
        return main.global_block().append_op(
            "sgd",
            inputs=dict(self._grad_inputs(grad), Param=[param.name],
                        LearningRate=[lr.name]),
            outputs={"ParamOut": [param.name]}, infer_shape=False)


class Momentum(Optimizer):
    def __init__(self, learning_rate, momentum=0.9, use_nesterov=False,
                 **kwargs):
        super().__init__(learning_rate, **kwargs)
        self._momentum = momentum
        self._use_nesterov = use_nesterov

    _SPARSE_CAPABLE = True

    def _append_optimize_op(self, main, startup, param, grad):
        vel = self._add_accumulator("velocity", param, main, startup)
        lr = self._lr_for_param(main, param)
        return main.global_block().append_op(
            "momentum",
            inputs=dict(self._grad_inputs(grad), Param=[param.name],
                        Velocity=[vel.name], LearningRate=[lr.name]),
            outputs={"ParamOut": [param.name], "VelocityOut": [vel.name]},
            attrs={"mu": self._momentum, "use_nesterov": self._use_nesterov},
            infer_shape=False)


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self._epsilon = epsilon

    _SPARSE_CAPABLE = True

    def _append_optimize_op(self, main, startup, param, grad):
        moment = self._add_accumulator("moment", param, main, startup)
        lr = self._lr_for_param(main, param)
        return main.global_block().append_op(
            "adagrad",
            inputs=dict(self._grad_inputs(grad), Param=[param.name],
                        Moment=[moment.name], LearningRate=[lr.name]),
            outputs={"ParamOut": [param.name], "MomentOut": [moment.name]},
            attrs={"epsilon": self._epsilon}, infer_shape=False)


class Adam(Optimizer):
    _SPARSE_CAPABLE = True  # lazy adam on touched rows

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _append_optimize_op(self, main, startup, param, grad):
        m1 = self._add_accumulator("moment1", param, main, startup)
        m2 = self._add_accumulator("moment2", param, main, startup)
        b1p = self._add_accumulator("beta1_pow", param, main, startup,
                                    fill_value=self._beta1, shape=[1])
        b2p = self._add_accumulator("beta2_pow", param, main, startup,
                                    fill_value=self._beta2, shape=[1])
        lr = self._lr_for_param(main, param)
        return main.global_block().append_op(
            "adam",
            inputs=dict(self._grad_inputs(grad), Param=[param.name],
                    Moment1=[m1.name], Moment2=[m2.name],
                    Beta1Pow=[b1p.name], Beta2Pow=[b2p.name],
                    LearningRate=[lr.name]),
            outputs={"ParamOut": [param.name], "Moment1Out": [m1.name],
                     "Moment2Out": [m2.name], "Beta1PowOut": [b1p.name],
                     "Beta2PowOut": [b2p.name]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon}, infer_shape=False)


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _append_optimize_op(self, main, startup, param, grad):
        m = self._add_accumulator("moment", param, main, startup)
        inf = self._add_accumulator("inf_norm", param, main, startup)
        b1p = self._add_accumulator("beta1_pow", param, main, startup,
                                    fill_value=self._beta1, shape=[1])
        lr = self._lr_for_param(main, param)
        return main.global_block().append_op(
            "adamax",
            inputs={"Param": [param.name], "Grad": [grad.name],
                    "Moment": [m.name], "InfNorm": [inf.name],
                    "Beta1Pow": [b1p.name], "LearningRate": [lr.name]},
            outputs={"ParamOut": [param.name], "MomentOut": [m.name],
                     "InfNormOut": [inf.name], "Beta1PowOut": [b1p.name]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon}, infer_shape=False)


class DecayedAdagrad(Optimizer):
    def __init__(self, learning_rate, decay=0.95, epsilon=1e-6, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self._decay, self._epsilon = decay, epsilon

    def _append_optimize_op(self, main, startup, param, grad):
        moment = self._add_accumulator("moment", param, main, startup)
        lr = self._lr_for_param(main, param)
        return main.global_block().append_op(
            "decayed_adagrad",
            inputs={"Param": [param.name], "Grad": [grad.name],
                    "Moment": [moment.name], "LearningRate": [lr.name]},
            outputs={"ParamOut": [param.name], "MomentOut": [moment.name]},
            attrs={"decay": self._decay, "epsilon": self._epsilon},
            infer_shape=False)


class AdaDelta(Optimizer):
    def __init__(self, learning_rate=1.0, rho=0.95, epsilon=1e-6, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self._rho, self._epsilon = rho, epsilon

    def _append_optimize_op(self, main, startup, param, grad):
        g2 = self._add_accumulator("avg_squared_grad", param, main, startup)
        u2 = self._add_accumulator("avg_squared_update", param, main,
                                   startup)
        return main.global_block().append_op(
            "adadelta",
            inputs={"Param": [param.name], "Grad": [grad.name],
                    "AvgSquaredGrad": [g2.name],
                    "AvgSquaredUpdate": [u2.name]},
            outputs={"ParamOut": [param.name], "AvgSquaredGradOut":
                     [g2.name], "AvgSquaredUpdateOut": [u2.name]},
            attrs={"rho": self._rho, "epsilon": self._epsilon},
            infer_shape=False)


class RMSProp(Optimizer):
    def __init__(self, learning_rate, decay=0.9, momentum=0.0,
                 epsilon=1e-10, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self._decay, self._momentum, self._epsilon = decay, momentum, epsilon

    def _append_optimize_op(self, main, startup, param, grad):
        ms = self._add_accumulator("mean_square", param, main, startup)
        mom = self._add_accumulator("moment", param, main, startup)
        lr = self._lr_for_param(main, param)
        return main.global_block().append_op(
            "rmsprop",
            inputs={"Param": [param.name], "Grad": [grad.name],
                    "MeanSquare": [ms.name], "Moment": [mom.name],
                    "LearningRate": [lr.name]},
            outputs={"ParamOut": [param.name], "MeanSquareOut": [ms.name],
                     "MomentOut": [mom.name]},
            attrs={"decay": self._decay, "momentum": self._momentum,
                   "epsilon": self._epsilon}, infer_shape=False)


class Ftrl(Optimizer):
    def __init__(self, learning_rate, l1=0.0, l2=0.0, lr_power=-0.5,
                 **kwargs):
        super().__init__(learning_rate, **kwargs)
        self._l1, self._l2, self._lr_power = l1, l2, lr_power

    def _append_optimize_op(self, main, startup, param, grad):
        sq = self._add_accumulator("squared", param, main, startup)
        lin = self._add_accumulator("linear", param, main, startup)
        lr = self._lr_for_param(main, param)
        return main.global_block().append_op(
            "ftrl",
            inputs={"Param": [param.name], "Grad": [grad.name],
                    "SquaredAccumulator": [sq.name],
                    "LinearAccumulator": [lin.name],
                    "LearningRate": [lr.name]},
            outputs={"ParamOut": [param.name], "SquaredAccumOut": [sq.name],
                     "LinearAccumOut": [lin.name]},
            attrs={"l1": self._l1, "l2": self._l2,
                   "lr_power": self._lr_power}, infer_shape=False)


# fluid-style aliases
SGDOptimizer = SGD
MomentumOptimizer = Momentum
AdagradOptimizer = Adagrad
AdamOptimizer = Adam
AdamaxOptimizer = Adamax
DecayedAdagradOptimizer = DecayedAdagrad
AdaDeltaOptimizer = AdaDelta
RMSPropOptimizer = RMSProp
FtrlOptimizer = Ftrl


def append_gradient_clip_ops(params_grads):
    """Apply per-parameter gradient_clip attrs (reference clip.py:102)."""
    from .clip import append_gradient_clip_ops as _impl
    return _impl(params_grads)


class ModelAverage:
    """Parameter averaging for evaluation (reference
    ``paddle/parameter/AverageOptimizer.h:23`` / fluid ModelAverage):
    accumulation ops are appended to the main program (in the same
    donated step as the optimizer update), and ``apply()`` swaps the
    averaged parameters in around evaluation, ``restore()`` (or leaving
    the context) swaps the trained values back.

    Differences from the reference, by design: the window is
    "since construction or the last reset_window()" — the reference's
    rolling min/max window bookkeeping collapses to an explicit reset,
    which composes with the one-XLA-step executor without in-graph
    conditionals.
    """

    def __init__(self, main_program=None, startup_program=None,
                 parameter_list=None):
        main = main_program or default_main_program()
        startup = startup_program or default_startup_program()
        block = main.global_block()
        sblock = startup.global_block()
        params = block.all_parameters()
        if parameter_list is not None:
            wanted = {p if isinstance(p, str) else p.name
                      for p in parameter_list}
            params = [p for p in params if p.name in wanted]
        self._param_names = [p.name for p in params]
        self._sums = {}
        cname = unique_name.generate("model_average_count")
        cvar = block.create_var(name=cname, shape=[1], dtype="float32",
                                persistable=True, stop_gradient=True)
        svar = sblock.create_var(name=cname, shape=[1], dtype="float32",
                                 persistable=True)
        ConstantInitializer(0.0)(svar, sblock)
        block.append_op("increment", inputs={"X": [cname]},
                        outputs={"Out": [cname]}, attrs={"step": 1.0},
                        infer_shape=False)
        self._count_name = cname
        for p in params:
            sname = unique_name.generate("%s_avg_sum" % p.name)
            # accumulate in f32 regardless of the parameter dtype: a
            # bf16 running sum loses the window's low-order contributions
            var = block.create_var(name=sname, shape=list(p.shape),
                                   dtype="float32", persistable=True,
                                   stop_gradient=True)
            sv = sblock.create_var(name=sname, shape=list(p.shape),
                                   dtype="float32", persistable=True)
            ConstantInitializer(0.0)(sv, sblock)
            # runs after the optimizer's update of p in the same block
            block.append_op("elementwise_add",
                            inputs={"X": [sname], "Y": [p.name]},
                            outputs={"Out": [sname]}, infer_shape=False)
            self._sums[p.name] = sname
        self._backup = None

    def apply(self, scope=None):
        """Swap averaged parameter values in (context manager)."""
        import contextlib

        @contextlib.contextmanager
        def _ctx():
            self._swap_in(scope)
            try:
                yield
            finally:
                self.restore(scope)
        return _ctx()

    def _swap_in(self, scope=None):
        from .core.scope import global_scope
        scope = scope or global_scope()
        count = float(np.asarray(scope.find_var(
            self._count_name)).ravel()[0])
        if count <= 0:
            raise RuntimeError("ModelAverage.apply before any step ran")
        self._backup = {}
        for pname in self._param_names:
            self._backup[pname] = scope.find_var(pname)
            avg = np.asarray(scope.find_var(self._sums[pname])) / count
            # swap in with the parameter's own dtype so the compiled
            # step's feed signature is unchanged on the next run
            pdtype = np.asarray(scope.find_var(pname)).dtype
            scope.set_var(pname, avg.astype(pdtype, copy=False))

    def restore(self, scope=None):
        from .core.scope import global_scope
        scope = scope or global_scope()
        if self._backup is None:
            return
        for pname, val in self._backup.items():
            scope.set_var(pname, val)
        self._backup = None

    def reset_window(self, scope=None):
        """Restart accumulation (the window boundary)."""
        from .core.scope import global_scope
        scope = scope or global_scope()
        scope.set_var(self._count_name,
                      np.zeros([1], dtype=np.float32))
        for pname in self._param_names:
            scope.set_var(self._sums[pname],
                          np.zeros(
                              np.asarray(scope.find_var(pname)).shape,
                              dtype=np.float32))
