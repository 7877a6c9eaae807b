"""Installation sanity check (reference python/paddle/fluid/install_check.py:45
run_check): builds a tiny fc net, runs one forward/backward step through the
single-device executor, then a data-parallel step through CompiledProgram on
however many devices the backend exposes (one TPU chip, the four chips of a
host, or N virtual devices under the CPU mesh).  With more than one device
visible, a failure of the data-parallel step is a failure of the check.
"""

from __future__ import annotations

import numpy as np

__all__ = ["run_check"]


def _build_simple_net(layers, initializer, param_attr):
    inp = layers.data(name="inp", shape=[2, 2], append_batch_size=False)
    fc = layers.fc(
        inp, size=3,
        param_attr=param_attr.ParamAttr(
            name="simple_fc_w",
            initializer=initializer.Constant(value=0.1)))
    out = layers.reduce_sum(fc)
    return inp, out


def run_check():
    """Verify the install end to end.  Prints progress like the reference
    (install_check.py:50 'Running Verify ... Program')."""
    import jax

    from paddle_tpu import (framework, initializer, layers, optimizer,
                            param_attr, unique_name)
    from paddle_tpu.core import executor as executor_mod
    from paddle_tpu.core.compiler import CompiledProgram
    from paddle_tpu.core.scope import Scope, scope_guard

    print("Running Verify paddle_tpu Program ... ")
    n_dev = len(jax.devices())

    def test_simple_exe():
        train_prog = framework.Program()
        startup_prog = framework.Program()
        with scope_guard(Scope()):
            with framework.program_guard(train_prog, startup_prog):
                with unique_name.guard():
                    from paddle_tpu import backward
                    inp, out = _build_simple_net(
                        layers, initializer, param_attr)
                    grads = backward.append_backward(out)
                    exe = executor_mod.Executor()
                    exe.run(startup_prog)
                    exe.run(train_prog,
                            feed={inp.name: np.array(
                                [[1.0, 2.0], [3.0, 4.0]], dtype=np.float32)},
                            fetch_list=[out.name, grads[0][1].name])

    def test_parallel_exe():
        train_prog = framework.Program()
        startup_prog = framework.Program()
        with scope_guard(Scope()):
            with framework.program_guard(train_prog, startup_prog):
                with unique_name.guard():
                    inp, out = _build_simple_net(
                        layers, initializer, param_attr)
                    loss = layers.mean(out)
                    optimizer.SGD(learning_rate=0.01).minimize(loss)
                    exe = executor_mod.Executor()
                    exe.run(startup_prog)
                    compiled = CompiledProgram(train_prog).with_data_parallel(
                        loss_name=loss.name)
                    feed_np = np.tile(
                        np.array([[1.0, 2.0], [3.0, 4.0]], dtype=np.float32),
                        (max(1, n_dev), 1))
                    exe.run(compiled, feed={inp.name: feed_np},
                            fetch_list=[loss.name])

    test_simple_exe()
    print("Your paddle_tpu works well on SINGLE device.")
    # no except: with more than one device visible a data-parallel
    # failure is a failure of the install, not a single-device success
    test_parallel_exe()
    if n_dev > 1:
        print("Your paddle_tpu works well on MULTIPLE devices "
              f"(data-parallel over {n_dev}).")
        print("Your paddle_tpu is installed successfully!")
    else:
        print("Your paddle_tpu is installed successfully ONLY for "
              "SINGLE device: one device is visible (set "
              "XLA_FLAGS=--xla_force_host_platform_device_count=N with "
              "JAX_PLATFORMS=cpu to emulate a mesh).")
