"""Layer: device.  Model FLOP/s utilization, %: operations the forward
and backward passes need per item (flops.py; recomputed work not
counted) x the measured window's rate / (chips x peak bf16 FLOP/s).
The cell's rate on a scale that compares cells.  Source: the harness's
host clock.
"""


def read(m):
    return (m["clocks"]["rate"] * m["work"]["flops_per_item"]
            / (m["chips"] * m["peaks"]["bf16_flops_per_s"]) * 100)
