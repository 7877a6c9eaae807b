"""Records the small chip trace that test_trace_reduce.py checks the
reduction on, and prints what a trace of this chip looks like (planes,
lines, event names and stats), which is how benchmarks/trace_reduce.py
was written: by hand, against this output.

Run on one chip:  python benchmarks/tests/record_trace.py
It writes chiprun_out/record_trace_1chip.xplane.pb; the copy kept in
benchmarks/tests/data/ is that file.  One small jitted step holds what
the reduction has to tell apart on one chip: a Pallas (Mosaic)
flash-attention call, a convolution, a matmul fusion, copies.  The
four-chip test trace is cut from a cell's own traced stretch
(trim_trace.py).  Not part of the benchmark's runs.
"""

import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData, ProfileOptions, TraceAnnotation


def main():
    from paddle_tpu.ops.pallas_kernels import flash_attention

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) != 1:
        sys.exit("record_trace.py records on one chip; found %s" % devs)
    print("devices", devs[0].device_kind, flush=True)

    def body(q, x, w, m):
        a = flash_attention(q, q, q, causal=True, impl="pallas")
        c = jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jnp.tanh(m @ m) + a.astype(jnp.float32).mean() \
            + c.astype(jnp.float32).mean()

    step = jax.jit(body)
    q = jnp.ones((2, 8, 1024, 64), jnp.bfloat16)
    x = jnp.ones((8, 56, 56, 64), jnp.bfloat16)
    w = jnp.ones((3, 3, 64, 64), jnp.bfloat16)
    m = jnp.ones((2048, 2048), jnp.bfloat16)
    for _ in range(2):
        float(step(q, x, w, m)[0, 0])

    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    os.makedirs("chiprun_out", exist_ok=True)
    out = "chiprun_out/_record_trace"
    shutil.rmtree(out, ignore_errors=True)
    jax.profiler.start_trace(out, profiler_options=opts)
    for i in range(4):
        with TraceAnnotation("bm:step"):
            with TraceAnnotation("bm:next"):
                time.sleep(0.002)
            with TraceAnnotation("bm:enqueue"):
                y = step(q, x, w, m)
            with TraceAnnotation("bm:fetch"):
                float(y[0, 0])
    jax.profiler.stop_trace()
    pb = glob.glob(out + "/plugins/profile/*/*.xplane.pb")[0]
    dst = "chiprun_out/record_trace_1chip.xplane.pb"
    shutil.copy(pb, dst)
    shutil.rmtree(out, ignore_errors=True)
    print("wrote", dst, os.path.getsize(dst), flush=True)

    pd = ProfileData.from_file(dst)
    for plane in pd.planes:
        lines = list(plane.lines)
        print("PLANE %r lines=%d" % (plane.name, len(lines)))
        for line in lines:
            evs = list(line.events)
            print("  LINE %r events=%d" % (line.name, len(evs)))
            show = evs if plane.name.startswith("/device") and \
                len(evs) < 80 else evs[:12]
            for e in show:
                print("     %r start=%d dur=%d stats=%r" % (
                    e.name, e.start_ns, e.duration_ns,
                    {k: (v if not isinstance(v, (str, bytes))
                         else v[:100]) for k, v in e.stats}))


if __name__ == "__main__":
    main()
