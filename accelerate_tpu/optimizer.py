"""Optimizer wrapper over optax.

Capability parity with the reference's ``optimizer.py`` (reference:
src/accelerate/optimizer.py — AcceleratedOptimizer :38: skips step/zero_grad
during accumulation :112/:155, grad-scaler step with skipped-step detection
:155-170, XLA grad all-reduce before step :142-148).

TPU-native redesign: the optimizer is an optax GradientTransformation; this
wrapper owns the (sharded) ``opt_state`` and a device-side gradient
accumulator. Cross-device gradient reduction needs NO explicit all-reduce —
the loss is a mean over the global (sharded) batch inside jit, so XLA emits
the reduction as part of the backward pass (the reference's
``xm.all_reduce`` at optimizer.py:142-148 has no equivalent here by design).

fp16 loss scaling is a pure state transition (precision.py) applied inside
the jitted step with a ``lax.cond``-style select: non-finite grads skip the
update and back off the scale, exactly like torch GradScaler.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from .precision import (
    GradScalerKwargs,
    LossScaleState,
    grads_finite,
    make_loss_scale,
    unscale_grads,
    update_loss_scale,
)
from .state import GradientState


class AcceleratedOptimizer:
    """Wraps an optax transformation with accumulation/scaling/skip logic.

    Created by ``Accelerator.prepare``; not usually constructed directly.
    """

    def __init__(
        self,
        tx,                                  # optax.GradientTransformation
        params=None,                         # initial params (to init opt_state)
        param_shardings=None,
        scaler_kwargs: Optional[GradScalerKwargs] = None,
        use_loss_scaling: bool = False,
        mesh=None,
        offload_to_host: bool = False,
        zero_sharding: bool = False,
        zero_min_size_to_shard: int = 2**11,
    ):
        self.tx = tx
        self.gradient_state = GradientState()
        self.mesh = mesh
        self.param_shardings = param_shardings
        self.offload_to_host = offload_to_host
        #: ZeRO-1/2: partition moment tensors over the dp (or fsdp) axis so
        #: each replica stores/updates 1/dp of the state (sharding.py
        #: infer_opt_state_shardings). Populated into opt_state_shardings at
        #: init_state time; the jitted update then carries explicit in/out
        #: shardings so GSPMD reduce-scatters grads, updates the local shard,
        #: and all-gathers params.
        self.zero_sharding = zero_sharding
        self.zero_min_size_to_shard = zero_min_size_to_shard
        self.opt_state_shardings = None
        self.opt_state = None
        self.acc_grads = None
        self._accumulated = 0
        self.scaler_kwargs = scaler_kwargs or GradScalerKwargs()
        self.loss_scale: Optional[LossScaleState] = make_loss_scale(self.scaler_kwargs, enabled=use_loss_scaling)
        self._step_was_skipped = False
        self._steps_applied = 0
        self._model = None  # back-ref set by Accelerator.prepare
        self._apply_jit = None
        self._grads_already_unscaled = False  # set by clip_grad_norm_ (fp16)
        # Fused-step bookkeeping: device-side finite flags, drained lazily so
        # the hot loop never forces a host sync (see steps_applied property).
        self._pending_finite: list = []
        self._last_finite = None
        if params is not None:
            self.init_state(params)

    # ------------------------------------------------------------------
    def init_state(self, params):
        """Initialize (sharded) optimizer state.

        opt_state leaves that mirror params (mu/nu) inherit the param
        shardings via jit's sharding propagation: we init under jit with
        out_shardings left to GSPMD.

        Models containing fp8 statistics params (ops/quant.py Fp8Dense) get
        the optimizer partitioned automatically: statistics leaves are
        overwritten with their updated values, never Adam-stepped.
        """
        from .ops.quant import wrap_optimizer_for_fp8

        if not getattr(self, "_fp8_wrapped", False):
            wrapped = wrap_optimizer_for_fp8(self.tx, params)
            if wrapped is not self.tx:
                self.tx = wrapped
                self._fp8_wrapped = True
        if self.param_shardings is not None:
            init = jax.jit(self.tx.init)
            self.opt_state = init(params)
        else:
            self.opt_state = self.tx.init(params)
        if self.mesh is not None:
            # Leaves with no data dependence on params (Adam's step count)
            # come back uncommitted on one device, while the fused step
            # returns them committed and replicated over the mesh — a
            # different jit cache key, i.e. a second full compile of the
            # train step at step 2. Commit them now.
            from .parallel.sharding import replicated_sharding

            repl = replicated_sharding(self.mesh)
            self.opt_state = jax.tree_util.tree_map(
                lambda x: (jax.device_put(x, repl)
                           if isinstance(x, jax.Array) and not x.committed else x),
                self.opt_state)
        if self.zero_sharding and self.mesh is not None and (
            self.mesh.shape.get("dp", 1) > 1 or self.mesh.shape.get("fsdp", 1) > 1
        ):
            from .parallel.sharding import infer_opt_state_shardings

            self.opt_state_shardings = infer_opt_state_shardings(
                self.opt_state,
                self.mesh,
                params=params,
                param_shardings=self._current_param_shardings(),
                min_size_to_shard=self.zero_min_size_to_shard,
            )
            # Committed placement: the 1/dp layout is established once here;
            # every jitted step after this reads/writes the local shard only.
            self.opt_state = jax.tree_util.tree_map(
                jax.device_put, self.opt_state, self.opt_state_shardings
            )
        if self.offload_to_host:
            from .parallel.host_offload import to_host

            self.opt_state = to_host(self.opt_state, self.mesh)
        self.acc_grads = None
        self._accumulated = 0

    def _current_param_shardings(self):
        """Param shardings from the bound model (preferred) or construction."""
        if self._model is not None and getattr(self._model, "param_shardings", None) is not None:
            return self._model.param_shardings
        return self.param_shardings

    # -- parity surface -------------------------------------------------
    @property
    def step_was_skipped(self) -> bool:
        """True if the last ``step()`` skipped (accumulating, or non-finite
        fp16 grads) (reference: optimizer.py:173). Reading this after a fused
        fp16 step forces a device sync on the finite flag."""
        if self._last_finite is not None:
            return not bool(jax.device_get(self._last_finite))
        return self._step_was_skipped

    @property
    def steps_applied(self) -> int:
        """Number of *applied* (finite) optimizer updates. Drains any pending
        fused-step finite flags (device sync) on read."""
        if self._pending_finite:
            flags = jax.device_get(self._pending_finite)
            self._steps_applied += int(sum(bool(f) for f in flags))
            self._pending_finite = []
        return self._steps_applied

    def zero_grad(self, set_to_none: bool = True):
        """Drop accumulated gradients (reference: optimizer.py:112 — no-op
        while accumulating)."""
        if self.gradient_state.sync_gradients:
            self.acc_grads = None
            self._accumulated = 0

    def accumulate_grads(self, grads):
        """Add a microbatch's gradients into the device-side accumulator."""
        if self.acc_grads is None:
            self.acc_grads = grads
        else:
            self.acc_grads = jax.tree_util.tree_map(jnp.add, self.acc_grads, grads)
        self._accumulated += 1

    def _build_apply(self):
        tx = self.tx
        has_scale = self.loss_scale is not None
        kwargs = self.scaler_kwargs

        def _apply(params, opt_state, grads, loss_scale, inv_scale):
            if has_scale:
                # inv_scale is 1/scale normally, or 1.0 when clip_grad_norm_
                # already unscaled the accumulated grads.
                grads = jax.tree_util.tree_map(
                    lambda g: (g.astype(jnp.float32) * inv_scale).astype(g.dtype), grads
                )
                finite = grads_finite(grads)
                updates, new_opt_state = tx.update(grads, opt_state, params)
                import optax

                new_params = optax.apply_updates(params, updates)
                # Select: skip everything if non-finite.
                new_params = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(finite, n, o), new_params, params
                )
                new_opt_state = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(finite, n, o) if hasattr(n, "dtype") else n,
                    new_opt_state,
                    opt_state,
                )
                new_scale = update_loss_scale(loss_scale, finite, kwargs)
                return new_params, new_opt_state, new_scale, finite
            else:
                updates, new_opt_state = tx.update(grads, opt_state, params)
                import optax

                new_params = optax.apply_updates(params, updates)
                return new_params, new_opt_state, loss_scale, jnp.asarray(True)

        if self.opt_state_shardings is not None:
            # ZeRO: pin params and opt_state in/out. Without the explicit
            # params out-sharding GSPMD would propagate the moments' dp
            # sharding onto the updated params (breaking the donation alias
            # and leaving params partitioned); with it, the update lowers to
            # reduce-scatter(grads) -> 1/dp Adam -> all-gather(params).
            from .parallel.sharding import replicated_sharding

            p_sh = self._current_param_shardings()
            if p_sh is None:
                repl = replicated_sharding(self.mesh)
                p_sh = jax.tree_util.tree_map(lambda _: repl, self._model.params)
            o_sh = self.opt_state_shardings
            return jax.jit(
                _apply,
                donate_argnums=(0, 1, 2),
                in_shardings=(p_sh, o_sh, None, None, None),
                out_shardings=(p_sh, o_sh, None, None),
            )
        return jax.jit(_apply, donate_argnums=(0, 1, 2))

    def step(self, closure=None):
        """Apply accumulated gradients if in a sync step (reference:
        optimizer.py:138-172)."""
        if not self.gradient_state.sync_gradients:
            self._step_was_skipped = True
            return
        if self.acc_grads is None:
            self._step_was_skipped = True
            return
        if self._model is None:
            raise RuntimeError("Optimizer is not bound to a model; use Accelerator.prepare.")
        if self._apply_jit is None:
            self._apply_jit = self._build_apply()
        if self.loss_scale is not None:
            inv_scale = (
                jnp.asarray(1.0, jnp.float32)
                if self._grads_already_unscaled
                else 1.0 / self.loss_scale.scale
            )
        else:
            inv_scale = jnp.asarray(1.0, jnp.float32)
        if self.offload_to_host:
            # Stream the state HBM-ward only for the (FLOP-light) update; the
            # backward that produced acc_grads ran without it resident.
            from .parallel.host_offload import to_device, to_host

            opt_in = to_device(self.opt_state, self.mesh)
        else:
            opt_in = self.opt_state
        params, opt_state, new_scale, finite = self._apply_jit(
            self._model.params, opt_in, self.acc_grads, self.loss_scale, inv_scale
        )
        if self.offload_to_host:
            opt_state = to_host(opt_state, self.mesh)
        self._grads_already_unscaled = False
        self._model.params = params
        self.opt_state = opt_state
        self.loss_scale = new_scale
        applied = bool(finite) if self.loss_scale is not None else True
        self._step_was_skipped = not applied
        self._last_finite = None  # eager path: the flag above is authoritative
        if applied:
            self._steps_applied += 1
        self.acc_grads = None
        self._accumulated = 0

    # -- checkpoint surface ---------------------------------------------
    def state_dict(self):
        """Host-side snapshot of optimizer state (reference parity)."""
        sd = {"opt_state": self.opt_state, "steps_applied": self._steps_applied}
        if self.loss_scale is not None:
            sd["loss_scale"] = self.loss_scale
        return sd

    def load_state_dict(self, sd):
        """Restore a state_dict snapshot."""
        self.opt_state = sd["opt_state"]
        if self.offload_to_host:
            from .parallel.host_offload import to_host

            self.opt_state = to_host(self.opt_state, self.mesh)
        self._steps_applied = sd.get("steps_applied", 0)
        if "loss_scale" in sd and sd["loss_scale"] is not None:
            ls = sd["loss_scale"]
            self.loss_scale = LossScaleState(
                scale=jnp.asarray(ls[0]), growth_tracker=jnp.asarray(ls[1]), fin_steps=jnp.asarray(ls[2])
            )

    def __repr__(self):
        return f"AcceleratedOptimizer({self.tx.__class__.__name__}, accumulated={self._accumulated})"
