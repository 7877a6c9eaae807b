"""Layer: kernels (ops/pallas_conv1d.py with the gates inside: the
whole mixer of an LFM2 conv layer between its two projections).  Share
of their roofline the convolution kernels reach, %: the least time for
the bytes and operations the gated convolution needs
(builders/lfm2_flops.py gated_conv_step: the projection's thirds B, C
and x read and y written once forward; they and dy read and dB, dC and
dx written once backward; the gates' and taps' products; memory-bound
by the count; the forward that a recompute segment runs again is not
counted, so it shows as lost share) over the device time of the calls
named pt_conv1d_fwd and pt_conv1d_bwd.  None where the builder counts
no such work (a cell whose convolutions have no gates) or the trace
holds none of the calls (a parent without them).  Source: the device
trace.
"""

import os
import runpy

CONV1D = ("pt_conv1d_fwd", "pt_conv1d_bwd")

_nk = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_named_kernels.py"))


def read(m):
    return _nk["roofline_pct"](m, CONV1D, "gated_conv")
