"""Optimizer ops — pure value-in/value-out updates; the executor writes
ParamOut back onto the Param variable (declared via in_place), matching the
reference's in-place optimizer kernels.

Reference parity: /root/reference/paddle/fluid/operators/optimizers/
  sgd_op.cc, momentum_op.cc (+LARS), adam_op.cc, adamax_op.cc, adagrad_op.cc,
  adadelta_op.cc, rmsprop_op.cc, ftrl_op.cc, lamb_op.cc,
  decayed_adagrad_op.cc, proximal_gd_op.cc.

Sparse (SelectedRows) gradients are densified by the caller on TPU (dense
segment-sum beats scatter on the MXU-adjacent memory system); a row-sliced
sparse path exists for the PS-style embedding service.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import REQUIRED, register_op
from paddle_tpu.core.scope import SelectedRows


def _dense_grad(g):
    if isinstance(g, SelectedRows):
        return g.to_dense()
    return g


@register_op("sgd", inputs=("Param", "Grad", "LearningRate"),
             outputs=("ParamOut",), differentiable=False,
             in_place={"ParamOut": "Param"})
def sgd(ins, attrs):
    g = _dense_grad(ins["Grad"])
    lr = ins["LearningRate"].astype(ins["Param"].dtype)
    return {"ParamOut": ins["Param"] - lr * g}


@register_op("momentum",
             inputs=("Param", "Grad", "Velocity", "LearningRate"),
             outputs=("ParamOut", "VelocityOut"), differentiable=False,
             attrs={"mu": REQUIRED, "use_nesterov": False},
             in_place={"ParamOut": "Param", "VelocityOut": "Velocity"})
def momentum(ins, attrs):
    p, v = ins["Param"], ins["Velocity"]
    g = _dense_grad(ins["Grad"])
    lr = ins["LearningRate"].astype(p.dtype)
    mu = attrs["mu"]
    v_out = mu * v + g
    if attrs["use_nesterov"]:
        p_out = p - (g + mu * v_out) * lr
    else:
        p_out = p - lr * v_out
    return {"ParamOut": p_out, "VelocityOut": v_out}


@register_op("lars_momentum",
             inputs=("Param", "Grad", "Velocity", "LearningRate"),
             outputs=("ParamOut", "VelocityOut"), differentiable=False,
             attrs={"mu": REQUIRED, "lars_coeff": 0.001,
                    "lars_weight_decay": 0.0005},
             in_place={"ParamOut": "Param", "VelocityOut": "Velocity"})
def lars_momentum(ins, attrs):
    p, v = ins["Param"], ins["Velocity"]
    g = _dense_grad(ins["Grad"])
    lr = ins["LearningRate"].astype(p.dtype)
    mu, coeff, wd = attrs["mu"], attrs["lars_coeff"], \
        attrs["lars_weight_decay"]
    p_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
    g_norm = jnp.sqrt(jnp.sum(jnp.square(g)))
    local_lr = lr * coeff * p_norm / (g_norm + wd * p_norm + 1e-12)
    v_out = mu * v + local_lr * (g + wd * p)
    return {"ParamOut": p - v_out, "VelocityOut": v_out}


@register_op("adam",
             inputs=("Param", "Grad", "Moment1", "Moment2", "Beta1Pow",
                     "Beta2Pow", "LearningRate"),
             outputs=("ParamOut", "Moment1Out", "Moment2Out",
                      "Beta1PowOut", "Beta2PowOut"),
             differentiable=False,
             attrs={"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8,
                    "lazy_mode": False},
             in_place={"ParamOut": "Param", "Moment1Out": "Moment1",
                       "Moment2Out": "Moment2", "Beta1PowOut": "Beta1Pow",
                       "Beta2PowOut": "Beta2Pow"})
def adam(ins, attrs):
    p, m1, m2 = ins["Param"], ins["Moment1"], ins["Moment2"]
    b1p, b2p = ins["Beta1Pow"], ins["Beta2Pow"]
    g = _dense_grad(ins["Grad"])
    lr = ins["LearningRate"].astype(p.dtype)
    b1, b2, eps = attrs["beta1"], attrs["beta2"], attrs["epsilon"]
    m1_out = b1 * m1 + (1 - b1) * g
    m2_out = b2 * m2 + (1 - b2) * jnp.square(g)
    lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
    p_out = p - lr_t * m1_out / (jnp.sqrt(m2_out) + eps)
    return {"ParamOut": p_out, "Moment1Out": m1_out, "Moment2Out": m2_out,
            "Beta1PowOut": b1p * b1, "Beta2PowOut": b2p * b2}


@register_op("fused_adam",
             inputs=("Param", "Grad", "Moment1", "Moment2", "Beta1Pow",
                     "Beta2Pow", "LearningRate"),
             outputs=("ParamOut", "Moment1Out", "Moment2Out",
                      "Beta1PowOut", "Beta2PowOut"),
             duplicable=("Param", "Grad", "Moment1", "Moment2",
                         "ParamOut", "Moment1Out", "Moment2Out"),
             differentiable=False,
             attrs={"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8},
             in_place={"ParamOut": "Param", "Moment1Out": "Moment1",
                       "Moment2Out": "Moment2", "Beta1PowOut": "Beta1Pow",
                       "Beta2PowOut": "Beta2Pow"})
def fused_adam(ins, attrs):
    """Multi-tensor Adam: ONE op over every (param, grad, m1, m2)
    tuple.  Each dtype group is flattened and concatenated so the whole
    optimizer tail is a single elementwise pass over one contiguous
    buffer instead of ~N small kernels XLA schedules independently —
    the Adam-tail A/B lever for the transformer batch-slide diagnosis
    (ROADMAP 1.3).  The update
    math matches the per-param `adam` op (lr_t computed in f32, cast
    per dtype group); beta pows are shared — every param sees the same
    step count."""
    import numpy as np

    ps, gs = ins["Param"], ins["Grad"]
    m1s, m2s = ins["Moment1"], ins["Moment2"]
    b1p, b2p = ins["Beta1Pow"], ins["Beta2Pow"]
    b1, b2, eps = attrs["beta1"], attrs["beta2"], attrs["epsilon"]
    lr32 = ins["LearningRate"].astype(jnp.float32)
    lr_t = lr32 * jnp.sqrt(1 - b2p.astype(jnp.float32)) \
        / (1 - b1p.astype(jnp.float32))
    n = len(ps)
    p_out, m1_out, m2_out = [None] * n, [None] * n, [None] * n
    groups: dict = {}
    for i, p in enumerate(ps):
        groups.setdefault(jnp.dtype(p.dtype), []).append(i)
    for dt, idxs in groups.items():
        sizes = [max(int(np.prod(ps[i].shape)), 1) for i in idxs]
        pc = jnp.concatenate([ps[i].reshape(-1) for i in idxs])
        gc = jnp.concatenate([
            _dense_grad(gs[i]).reshape(-1).astype(dt) for i in idxs])
        m1c = jnp.concatenate([m1s[i].reshape(-1) for i in idxs])
        m2c = jnp.concatenate([m2s[i].reshape(-1) for i in idxs])
        m1n = b1 * m1c + (1 - b1) * gc
        m2n = b2 * m2c + (1 - b2) * jnp.square(gc)
        pn = pc - lr_t.astype(dt) * m1n / (jnp.sqrt(m2n) + eps)
        offs = np.cumsum([0] + sizes)
        for j, i in enumerate(idxs):
            sl = slice(int(offs[j]), int(offs[j + 1]))
            p_out[i] = pn[sl].reshape(ps[i].shape)
            m1_out[i] = m1n[sl].reshape(ps[i].shape)
            m2_out[i] = m2n[sl].reshape(ps[i].shape)
    return {"ParamOut": p_out, "Moment1Out": m1_out,
            "Moment2Out": m2_out, "Beta1PowOut": b1p * b1,
            "Beta2PowOut": b2p * b2}


@register_op("adamw",
             inputs=("Param", "Grad", "Moment1", "Moment2", "Beta1Pow",
                     "Beta2Pow", "LearningRate"),
             outputs=("ParamOut", "Moment1Out", "Moment2Out",
                      "Beta1PowOut", "Beta2PowOut"),
             differentiable=False,
             attrs={"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8,
                    "weight_decay": 0.01},
             in_place={"ParamOut": "Param", "Moment1Out": "Moment1",
                       "Moment2Out": "Moment2", "Beta1PowOut": "Beta1Pow",
                       "Beta2PowOut": "Beta2Pow"})
def adamw(ins, attrs):
    p = ins["Param"]
    lr = ins["LearningRate"].astype(p.dtype)
    out = adam({**ins, "Param": p}, {k: attrs[k] for k in
                                     ("beta1", "beta2", "epsilon")}
               | {"lazy_mode": False})
    out["ParamOut"] = out["ParamOut"] - lr * attrs["weight_decay"] * p
    return out


@register_op("adagrad", inputs=("Param", "Grad", "Moment", "LearningRate"),
             outputs=("ParamOut", "MomentOut"), differentiable=False,
             attrs={"epsilon": 1e-6},
             in_place={"ParamOut": "Param", "MomentOut": "Moment"})
def adagrad(ins, attrs):
    p, m = ins["Param"], ins["Moment"]
    g = _dense_grad(ins["Grad"])
    lr = ins["LearningRate"].astype(p.dtype)
    m_out = m + jnp.square(g)
    p_out = p - lr * g / (jnp.sqrt(m_out) + attrs["epsilon"])
    return {"ParamOut": p_out, "MomentOut": m_out}


@register_op("adadelta",
             inputs=("Param", "Grad", "AvgSquaredGrad",
                     "AvgSquaredUpdate"),
             outputs=("ParamOut", "AvgSquaredGradOut",
                      "AvgSquaredUpdateOut"),
             differentiable=False,
             attrs={"rho": 0.95, "epsilon": 1e-6},
             in_place={"ParamOut": "Param",
                       "AvgSquaredGradOut": "AvgSquaredGrad",
                       "AvgSquaredUpdateOut": "AvgSquaredUpdate"})
def adadelta(ins, attrs):
    p, asg, asu = ins["Param"], ins["AvgSquaredGrad"], \
        ins["AvgSquaredUpdate"]
    g = _dense_grad(ins["Grad"])
    rho, eps = attrs["rho"], attrs["epsilon"]
    asg_out = rho * asg + (1 - rho) * jnp.square(g)
    update = -jnp.sqrt((asu + eps) / (asg_out + eps)) * g
    asu_out = rho * asu + (1 - rho) * jnp.square(update)
    return {"ParamOut": p + update, "AvgSquaredGradOut": asg_out,
            "AvgSquaredUpdateOut": asu_out}


@register_op("rmsprop",
             inputs=("Param", "Grad", "MeanSquare", "MeanGrad", "Moment",
                     "LearningRate"),
             outputs=("ParamOut", "MeanSquareOut", "MeanGradOut",
                      "MomentOut"),
             differentiable=False,
             attrs={"decay": 0.9, "momentum": 0.0, "epsilon": 1e-10,
                    "centered": False},
             in_place={"ParamOut": "Param", "MeanSquareOut": "MeanSquare",
                       "MeanGradOut": "MeanGrad", "MomentOut": "Moment"})
def rmsprop(ins, attrs):
    p, ms, mg, mom = ins["Param"], ins["MeanSquare"], ins["MeanGrad"], \
        ins["Moment"]
    g = _dense_grad(ins["Grad"])
    lr = ins["LearningRate"].astype(p.dtype)
    rho, eps = attrs["decay"], attrs["epsilon"]
    ms_out = rho * ms + (1 - rho) * jnp.square(g)
    if attrs["centered"]:
        mg_out = rho * mg + (1 - rho) * g
        denom = ms_out - jnp.square(mg_out) + eps
    else:
        mg_out = mg
        denom = ms_out + eps
    mom_out = attrs["momentum"] * mom + lr * g / jnp.sqrt(denom)
    return {"ParamOut": p - mom_out, "MeanSquareOut": ms_out,
            "MeanGradOut": mg_out, "MomentOut": mom_out}


@register_op("adamax",
             inputs=("Param", "Grad", "Moment", "InfNorm", "Beta1Pow",
                     "LearningRate"),
             outputs=("ParamOut", "MomentOut", "InfNormOut"),
             differentiable=False,
             attrs={"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8},
             in_place={"ParamOut": "Param", "MomentOut": "Moment",
                       "InfNormOut": "InfNorm"})
def adamax(ins, attrs):
    p, m, inf = ins["Param"], ins["Moment"], ins["InfNorm"]
    g = _dense_grad(ins["Grad"])
    lr = ins["LearningRate"].astype(p.dtype)
    b1, b2, eps = attrs["beta1"], attrs["beta2"], attrs["epsilon"]
    m_out = b1 * m + (1 - b1) * g
    inf_out = jnp.maximum(b2 * inf, jnp.abs(g) + eps)
    lr_t = lr / (1 - ins["Beta1Pow"])
    return {"ParamOut": p - lr_t * m_out / inf_out, "MomentOut": m_out,
            "InfNormOut": inf_out}


@register_op("ftrl",
             inputs=("Param", "Grad", "SquaredAccumulator",
                     "LinearAccumulator", "LearningRate"),
             outputs=("ParamOut", "SquaredAccumOut", "LinearAccumOut"),
             differentiable=False,
             attrs={"l1": 0.0, "l2": 0.0, "lr_power": -0.5},
             in_place={"ParamOut": "Param",
                       "SquaredAccumOut": "SquaredAccumulator",
                       "LinearAccumOut": "LinearAccumulator"})
def ftrl(ins, attrs):
    p, sq, lin = ins["Param"], ins["SquaredAccumulator"], \
        ins["LinearAccumulator"]
    g = _dense_grad(ins["Grad"])
    lr = ins["LearningRate"].astype(p.dtype)
    l1, l2, lrp = attrs["l1"], attrs["l2"], attrs["lr_power"]
    sq_out = sq + jnp.square(g)
    sigma = (jnp.power(sq_out, -lrp) - jnp.power(sq, -lrp)) / lr
    lin_out = lin + g - sigma * p
    x = -lin_out + jnp.clip(lin_out, -l1, l1)
    y = jnp.power(sq_out, -lrp) / lr + 2 * l2
    return {"ParamOut": x / y, "SquaredAccumOut": sq_out,
            "LinearAccumOut": lin_out}


@register_op("lamb",
             inputs=("Param", "Grad", "Moment1", "Moment2", "Beta1Pow",
                     "Beta2Pow", "LearningRate"),
             outputs=("ParamOut", "Moment1Out", "Moment2Out",
                      "Beta1PowOut", "Beta2PowOut"),
             differentiable=False,
             attrs={"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-6,
                    "weight_decay": 0.01},
             in_place={"ParamOut": "Param", "Moment1Out": "Moment1",
                       "Moment2Out": "Moment2", "Beta1PowOut": "Beta1Pow",
                       "Beta2PowOut": "Beta2Pow"})
def lamb(ins, attrs):
    p, m1, m2 = ins["Param"], ins["Moment1"], ins["Moment2"]
    b1p, b2p = ins["Beta1Pow"], ins["Beta2Pow"]
    g = _dense_grad(ins["Grad"])
    lr = ins["LearningRate"].astype(p.dtype)
    b1, b2, eps, wd = attrs["beta1"], attrs["beta2"], attrs["epsilon"], \
        attrs["weight_decay"]
    m1_out = b1 * m1 + (1 - b1) * g
    m2_out = b2 * m2 + (1 - b2) * jnp.square(g)
    m1_hat = m1_out / (1 - b1p)
    m2_hat = m2_out / (1 - b2p)
    r = m1_hat / (jnp.sqrt(m2_hat) + eps) + wd * p
    p_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
    r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
    trust = jnp.where(
        (p_norm > 0) & (r_norm > 0), p_norm / r_norm, 1.0
    )
    return {"ParamOut": p - lr * trust * r, "Moment1Out": m1_out,
            "Moment2Out": m2_out, "Beta1PowOut": b1p * b1,
            "Beta2PowOut": b2p * b2}


@register_op("decayed_adagrad",
             inputs=("Param", "Grad", "Moment", "LearningRate"),
             outputs=("ParamOut", "MomentOut"), differentiable=False,
             attrs={"decay": 0.95, "epsilon": 1e-6},
             in_place={"ParamOut": "Param", "MomentOut": "Moment"})
def decayed_adagrad(ins, attrs):
    p, m = ins["Param"], ins["Moment"]
    g = _dense_grad(ins["Grad"])
    lr = ins["LearningRate"].astype(p.dtype)
    m_out = attrs["decay"] * m + (1 - attrs["decay"]) * jnp.square(g)
    return {"ParamOut": p - lr * g / (jnp.sqrt(m_out) + attrs["epsilon"]),
            "MomentOut": m_out}


@register_op("lookahead_update",
             inputs=("Param", "Slow", "Step"),
             outputs=("ParamOut", "SlowOut"), differentiable=False,
             attrs={"alpha": 0.5, "k": 5},
             in_place={"ParamOut": "Param", "SlowOut": "Slow"})
def lookahead_update(ins, attrs):
    """Every k steps: slow += alpha*(fast-slow); fast = slow.  The
    k-step schedule is a where() select so it compiles into the jitted
    step (reference incubate LookaheadOptimizer host-side variant)."""
    p, slow = ins["Param"], ins["Slow"]
    step = ins["Step"].reshape(()).astype(jnp.float32)
    k = float(attrs["k"])
    sync = jnp.mod(step, k) == 0.0
    new_slow = slow + attrs["alpha"] * (p - slow)
    slow_out = jnp.where(sync, new_slow, slow)
    p_out = jnp.where(sync, new_slow, p)
    return {"ParamOut": p_out, "SlowOut": slow_out}


@register_op("dgc_momentum",
             inputs=("Param", "Grad", "U", "V", "Velocity",
                     "LearningRate", "Step"),
             outputs=("ParamOut", "UOut", "VOut", "VelocityOut"),
             differentiable=False, optional=("Step",),
             attrs={"momentum": REQUIRED, "sparsity": 0.999,
                    "rampup_begin_step": 0, "use_nesterov": False},
             in_place={"ParamOut": "Param", "UOut": "U", "VOut": "V",
                       "VelocityOut": "Velocity"})
def dgc_momentum(ins, attrs):
    """DGC (reference dgc_op.cc + DGCMomentumOptimizer): local gradient
    accumulation u, error-feedback buffer v, top-k mask by |v|, masked
    momentum update; dense warmup until rampup_begin_step.  The
    'encoded' gradient stays dense (mask*value) — TPU prefers dense
    top-k over scatter."""
    p, g = ins["Param"], _dense_grad(ins["Grad"])
    u, v, vel = ins["U"], ins["V"], ins["Velocity"]
    lr = ins["LearningRate"].astype(p.dtype)
    m = attrs["momentum"]
    u = m * u + g                      # momentum correction
    v = v + u
    flat = jnp.abs(v).reshape(-1)
    from paddle_tpu.parallel.dgc import dgc_top_k_count

    k = dgc_top_k_count(flat.shape[0], attrs["sparsity"])
    thresh = jax.lax.top_k(flat, k)[0][-1]
    mask = (jnp.abs(v) >= thresh).astype(p.dtype)
    if attrs["rampup_begin_step"] > 0 and "Step" not in ins:
        raise ValueError(
            "dgc_momentum: rampup_begin_step > 0 requires the Step "
            "input (the optimizer wires it automatically)")
    if "Step" in ins and attrs["rampup_begin_step"] > 0:
        # dense warmup: before rampup_begin_step every component passes
        step = ins["Step"].reshape(()).astype(jnp.float32)
        warm = step <= float(attrs["rampup_begin_step"])
        mask = jnp.where(warm, jnp.ones_like(mask), mask)
    sparse_grad = v * mask
    v = v * (1.0 - mask)               # error feedback: keep the rest
    u = u * (1.0 - mask)
    vel_out = m * vel + sparse_grad
    if attrs["use_nesterov"]:
        p_out = p - (sparse_grad + m * vel_out) * lr
    else:
        p_out = p - lr * vel_out
    return {"ParamOut": p_out, "UOut": u, "VOut": v,
            "VelocityOut": vel_out}


@register_op("model_average_update",
             inputs=("Params", "Sums", "Count", "Total"),
             outputs=("SumsOut", "CountOut"),
             duplicable=("Params", "Sums", "SumsOut"),
             differentiable=False,
             attrs={"average_window_rate": 0.15,
                    "min_average_window": 100,
                    "max_average_window": 10000},
             in_place={"SumsOut": "Sums", "CountOut": "Count"})
def model_average_update(ins, attrs):
    """Bounded-window parameter-sum accumulation (reference
    ModelAverage sum_1/2/3 rotation, optimizer.py:2244 — simplified to
    a single sum that restarts when the window limit is hit).  The
    effective window is max(min_w, min(max_w, rate * total_updates))."""
    params, sums = ins["Params"], ins["Sums"]
    count = ins["Count"].reshape(())
    total = ins["Total"].reshape(())
    window = jnp.clip(attrs["average_window_rate"] * total,
                      float(attrs["min_average_window"]),
                      float(attrs["max_average_window"]))
    restart = count >= window
    new_count = jnp.where(restart, 1.0, count + 1.0)
    new_sums = [jnp.where(restart, p, s + p)
                for p, s in zip(params, sums)]
    return {"SumsOut": new_sums, "CountOut": new_count.reshape(1)}


@register_op("proximal_gd",
             inputs=("Param", "Grad", "LearningRate"),
             outputs=("ParamOut",), differentiable=False,
             attrs={"l1": 0.0, "l2": 0.0},
             in_place={"ParamOut": "Param"})
def proximal_gd(ins, attrs):
    """optimizers/proximal_gd_op.h: prox_param = p - lr*g, then the
    l1 soft-threshold / l2 shrink proximal step."""
    p, g = ins["Param"], _dense_grad(ins["Grad"])
    lr = ins["LearningRate"].reshape(()).astype(p.dtype)
    l1 = jnp.asarray(attrs["l1"], p.dtype)
    l2 = jnp.asarray(attrs["l2"], p.dtype)
    prox = p - lr * g
    if attrs["l1"] > 0:
        out = (jnp.sign(prox)
               * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0)
               / (1.0 + lr * l2))
    else:
        out = prox / (1.0 + lr * l2)
    return {"ParamOut": out}


@register_op("proximal_adagrad",
             inputs=("Param", "Moment", "Grad", "LearningRate"),
             outputs=("ParamOut", "MomentOut"), differentiable=False,
             attrs={"l1": 0.0, "l2": 0.0},
             in_place={"ParamOut": "Param", "MomentOut": "Moment"})
def proximal_adagrad(ins, attrs):
    """optimizers/proximal_adagrad_op.h: adagrad accumulator + the same
    proximal step with per-element lr/sqrt(m)."""
    p, g = ins["Param"], _dense_grad(ins["Grad"])
    m = ins["Moment"]
    lr = ins["LearningRate"].reshape(()).astype(p.dtype)
    l1 = jnp.asarray(attrs["l1"], p.dtype)
    l2 = jnp.asarray(attrs["l2"], p.dtype)
    m_out = m + g * g
    prox = p - lr * g / jnp.sqrt(m_out)
    if attrs["l1"] > 0:
        out = (jnp.sign(prox)
               * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0)
               / (1.0 + lr * l2))
    else:
        out = prox / (1.0 + lr * l2)
    return {"ParamOut": out, "MomentOut": m_out}


def _dgc_rampup_sparsity(step, sparsity_steps, rampup_step):
    """Sparsity warmup schedule, matching dgc_op.h get_period_sparcity:
    idx = int(cur_step * len(sparsity) / rampup_steps) over the ABSOLUTE
    step count, pinned to 0.999 once idx runs past the vector end."""
    phases = len(sparsity_steps)
    idx = (step * phases / max(rampup_step, 1.0)).astype(jnp.int32)
    in_vec = jnp.asarray(sparsity_steps)[
        jnp.clip(idx, 0, phases - 1)]
    return jnp.where(idx >= phases, 0.999, in_vec)


@register_op("dgc",
             inputs=("U", "V", "Grad", "current_step"),
             outputs=("U_out", "V_out", "EncodeGrad", "Grad_out", "k"),
             differentiable=False,
             attrs={"m": 0.9, "use_nesterov": False,
                    "sparsity": [0.999], "rampup_begin_step": 0.0,
                    "rampup_step": 1.0},
             in_place={"U_out": "U", "V_out": "V"})
def dgc(ins, attrs):
    """dgc_op.cc: the standalone sparsify stage (momentum correction +
    error feedback + top-k).  EncodeGrad is the dense masked gradient —
    the actual sparse wire exchange is parallel/dgc.py dgc_allreduce."""
    g = _dense_grad(ins["Grad"])
    u, v = ins["U"], ins["V"]
    step = ins["current_step"].reshape(()).astype(jnp.float32)
    m = attrs["m"]
    if attrs["use_nesterov"]:
        # dgc_op.h:89-97: u = m*(u+g); v = u + v + g (v_out aliases v,
        # so both adds read the freshly written u)
        u = m * (u + g)
        v = u + v + g
    else:
        # dgc_op.h:99-104: u = m*u + g; v = u + v
        u = m * u + g
        v = v + u
    sparsity = _dgc_rampup_sparsity(
        step, [float(s) for s in attrs["sparsity"]],
        float(attrs["rampup_step"]))
    n = v.size
    # the scheduled sparsity is a traced value, so k is dynamic: take
    # the threshold at the k-th largest |v| via a full descending sort
    # + dynamic_slice (static shapes throughout, jittable)
    flat = jnp.abs(v).reshape(-1)
    sorted_desc = jnp.sort(flat)[::-1]
    k_sched = jnp.clip(
        (n * (1.0 - sparsity)).astype(jnp.int32), 1, n)
    kth = jax.lax.dynamic_index_in_dim(sorted_desc, k_sched - 1,
                                       keepdims=False)
    warm = step < float(attrs["rampup_begin_step"])
    mask = jnp.where(warm, jnp.ones_like(v, dtype=bool),
                     jnp.abs(v) >= kth)
    encode = jnp.where(mask, v, 0.0)
    u_out = jnp.where(mask, 0.0, u)
    v_out = jnp.where(mask, 0.0, v)
    return {"U_out": u_out, "V_out": v_out, "EncodeGrad": encode,
            "Grad_out": encode,
            "k": k_sched.astype(jnp.float32).reshape(1)}


@register_op("dgc_clip_by_norm",
             inputs=("X", "current_step"), outputs=("Out",),
             differentiable=False,
             attrs={"max_norm": REQUIRED, "rampup_begin_step": 0.0})
def dgc_clip_by_norm(ins, attrs):
    """dgc_clip_by_norm_op.cc: clip_by_norm that only engages after
    rampup_begin_step (identity during dense warmup)."""
    x = ins["X"]
    step = ins["current_step"].reshape(()).astype(jnp.float32)
    norm = jnp.sqrt(jnp.sum(x * x))
    max_norm = jnp.asarray(attrs["max_norm"], x.dtype)
    clipped = x * jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return {"Out": jnp.where(step < float(attrs["rampup_begin_step"]),
                             x, clipped)}
