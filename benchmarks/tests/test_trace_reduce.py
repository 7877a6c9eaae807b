"""trace_reduce.py: interval arithmetic on synthetic cases, naming on
instruction texts copied from a chip trace, and the whole reduction on
small traces recorded on the v5e (tests/data/, made by
record_trace.py and trim_trace.py) with the numbers they give pinned."""

import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


# -- intervals ---------------------------------------------------------------

def test_union_merges_overlapping_and_touching():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == \
        [(0, 4), (5, 7)]
    assert tr.total([(0, 10), (2, 3), (8, 12)]) == 12
    assert tr.total([]) == 0


def test_gaps_are_what_no_interval_covers():
    busy = [(2, 4), (3, 6), (8, 9)]
    assert tr.gaps(busy, 0, 10) == [(0, 2), (6, 8), (9, 10)]
    assert tr.gaps(busy, 3, 8) == [(6, 8)]
    assert tr.gaps([], 0, 5) == [(0, 5)]


def test_subtract_and_overlap():
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert tr.subtract([(0, 4)], [(0, 4)]) == []
    assert tr.overlap([(0, 10)], [(2, 3), (5, 7), (9, 20)]) == 4


def test_exposed_collective_is_the_part_no_other_op_covers():
    # an async all-gather in flight 0..100 while compute runs 10..40 and
    # 60..90: exposed 0..10, 40..60, 90..100 = 40; a synchronous
    # all-reduce 120..150 with nothing beside it: all 30 exposed
    coll = [(0, 100), (120, 150)]
    other = [(10, 40), (60, 90), (150, 170)]
    assert tr.total(tr.subtract(coll, other)) == 40 + 30
    assert tr.total(coll) == 130


# -- naming ------------------------------------------------------------------

FLASH = ('%body.1 = (bf16[16,1024,64]{2,1,0:T(8,128)(2,1)S(1)}, '
         'f32[16,1024,128]{2,1,0:T(8,128)}) custom-call(bf16[16,1024,64]'
         '{2,1,0:T(8,128)(2,1)S(1)} %bitcast.4), '
         'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
FUSION = ('%convert_reduce_fusion = f32[]{:T(128)} fusion(bf16[3,3,64,64]'
          '{3,2,1,0:T(8,128)(2,1)} %w.1, bf16[8,56,56,64]{3,2,1,0} %x.1), '
          'kind=kOutput, calls=%fused_computation.1')
COPY_START = ('%copy-start = (f32[2048,2048]{1,0:T(8,128)S(1)}, '
              'f32[2048,2048]{1,0:T(8,128)}, u32[]{:S(2)}) copy-start('
              'f32[2048,2048]{1,0:T(8,128)} %args_0_.1), '
              'cross_program_prefetch_index=0')
ALL_REDUCE = ('%all-reduce-start.3 = f32[512,512]{1,0} all-reduce-start('
              'f32[512,512]{1,0} %fusion.9), channel_id=4, '
              'replica_groups={{0,1},{2,3}}, to_apply=%add')
HLO = """
%fused_computation.1 (p0: bf16[3,3,64,64], p1: bf16[8,56,56,64]) -> f32[] {
  %p0 = bf16[3,3,64,64] parameter(0)
  %convolution.5 = bf16[8,56,56,64] convolution(%p1, %p0), window={size=3x3}
  ROOT %reduce = f32[] reduce(%convolution.5)
}

%fused_computation.3 (p: bf16[16,1024,64]) -> f32[] {
  ROOT %reduce.2 = f32[] reduce(%p)
}
"""


def test_opcode_skips_a_tuple_shape():
    assert tr.opcode(FLASH) == "custom-call"
    assert tr.opcode(FUSION) == "fusion"
    assert tr.opcode(COPY_START) == "copy-start"
    assert tr.opcode(ALL_REDUCE) == "all-reduce-start"
    assert tr.instruction(FLASH) == "body.1"


def test_classify():
    conv = tr.conv_computations(HLO)
    assert conv == {"fused_computation.1"}
    assert tr.classify(FLASH) == "mosaic"
    assert tr.classify(FUSION, conv) == "convolution"
    # without the compiled step's text a fusion is just a fusion
    assert tr.classify(FUSION) == "fusion"
    assert tr.classify(FUSION.replace("computation.1", "computation.3"),
                       conv) == "fusion"
    assert tr.classify(COPY_START) == "copy"
    assert tr.classify(ALL_REDUCE) == "collective"
    assert tr.classify(ALL_REDUCE.replace("-start", "-done")) == \
        "collective"
    assert tr.group_name(FLASH, "mosaic") == "mosaic:body"
    assert tr.group_name(ALL_REDUCE, "collective") == \
        "collective:all-reduce-start"


# -- a synthetic trace through the whole reduction ----------------------------

def _synthetic():
    """Three runs of a 100 ns step, 150 ns apart; each holds a flash
    call, a conv fusion and an all-reduce in flight over half of it."""
    modules, ops, spans, host = [], [], [], []
    for i in range(3):
        t = 1000 + 150 * i
        modules.append(("jit_step(1)", t, t + 100))
        modules.append(("jit_small(2)", t + 110, t + 115))
        ops += [(FLASH, t, t + 40), (FUSION, t + 40, t + 70),
                (ALL_REDUCE, t + 70, t + 72),
                (ALL_REDUCE.replace("-start", "-done"), t + 90, t + 100),
                (COPY_START.replace("-start", ""), t + 110, t + 115)]
        spans.append((ALL_REDUCE, t + 70, t + 100))
        ops.append((FUSION.replace("computation.1", "computation.3"),
                    t + 72, t + 90))
        # the host's clock runs 500 ns ahead of the device's: the
        # launch is seen 10 before the run starts, its end 10 after
        host += [("tpu::System::Execute", t + 490, t + 495),
                 ("tpu::System::Execute=>Done", t + 610, t + 612),
                 ("bm:enqueue", t + 480, t + 505),
                 ("bm:fetch", t + 505, t + 620),
                 ("bm:next", t + 622, t + 628)]
    return {"devices": {"/device:TPU:0": {"modules": modules, "ops": ops,
                                          "async": spans}},
            "host": host}


def test_reduce_synthetic():
    r = tr.reduce(_synthetic(), HLO)
    d = r["devices"]["/device:TPU:0"]
    assert d["module"] == "jit_step(1)" and d["steps"] == 2
    # window: start of the first run to start of the last
    assert d["window_ns"] == 300
    # per period: busy 0..100 and 110..115 of 150
    assert d["busy_ns"] == 2 * 105
    assert d["category_ns"] == {"mosaic": 80, "convolution": 60,
                                "collective": 2 * (2 + 10),
                                "fusion": 2 * 18, "copy": 10}
    # in flight 70..100 = 30; another op runs 72..90: 12 exposed
    assert d["collective_ns"] == 60
    assert d["collective_exposed_ns"] == 24
    # between the bounds 490 (launch) and 510 (seen done), give or
    # take 10
    assert d["host_offset_ns"] == 500
    assert d["host_offset_slack_ns"] == 10
    # the gaps, shared out: 100..110 while the host waits for the
    # fetch; 115..150 while it is in fetch (to 120), in next(loader)
    # (122..128), in the next exe.run before its launch (130..150),
    # and between those (4)
    assert d["idle_by_phase_ns"] == {"fetch": 2 * 15, "next": 2 * 6,
                                     "between steps": 2 * 4,
                                     "enqueue": 2 * 20}
    # and labelled by the phase that covers most of each
    assert d["idle_gaps"] == [("enqueue", 35), ("enqueue", 35),
                              ("fetch", 10), ("fetch", 10)]
    assert r["busy_s"] == pytest.approx(210e-9)
    assert r["window_s"] == pytest.approx(300e-9)
    assert r["device_ops"][0] == ["mosaic:body", pytest.approx(80e-9)]
    assert tr.per_step_ms(r, "category_ns", "mosaic") == \
        pytest.approx(40e-6)
    assert tr.per_step_ms(r, "collective_exposed_ns") == \
        pytest.approx(12e-6)


def test_host_offset_bounds():
    runs = [(100, 200), (300, 400)]
    # launches alone: the smallest shift that starts no run early
    assert tr.host_offset_ns(runs, [(150, 151), (345, 346)]) == (50, None)
    # with completions: midway, and half the distance as slack
    assert tr.host_offset_ns(runs, [(150, 151), (345, 346)],
                             [(270, 271), (480, 481)]) == (60, 10)
    # more launches than runs: matched from the end
    assert tr.host_offset_ns(runs[1:], [(150, 151), (345, 346)]) == \
        (45, None)
    assert tr.host_offset_ns(runs, []) == (None, None)


def test_too_few_steps_reduce_to_nothing():
    t = _synthetic()
    t["devices"]["/device:TPU:0"]["modules"] = \
        t["devices"]["/device:TPU:0"]["modules"][:4]
    assert tr.reduce(t) is None
    assert tr.reduce({"devices": {}, "host": []}) is None


# -- the recorded traces -----------------------------------------------------

def test_recorded_one_chip_trace():
    """4 runs of record_trace.py's step on one v5e chip: a flash
    forward call (73 us), a 3x3 convolution fused with its reduction
    (35 us), a 2048^3 matmul fusion (92 us)."""
    trace = tr.read_xplane(os.path.join(DATA,
                                        "record_trace_1chip.xplane.pb"))
    assert list(trace["devices"]) == ["/device:TPU:0"]
    r = tr.reduce(trace)
    d = r["devices"][r["first"]]
    assert d["module"].startswith("jit_body(") and d["steps"] == 3
    assert d["window_ns"] == 14076931
    assert d["busy_ns"] == 689970
    assert d["category_ns"] == {"copy": 75533, "fusion": 391387,
                                "mosaic": 218692}
    assert d["op_ns"]["mosaic:body"] == 218692
    assert d["collective_ns"] == 0 and d["collective_exposed_ns"] == 0
    # the device's clock ran 1.9 ms behind the host's, give or take
    # 0.7: three programs a step, so the first launch of each three is
    # the step's and the last completion bounds its end loosely
    assert d["host_offset_ns"] == 1921847.5
    assert d["host_offset_slack_ns"] == 666128.5
    # record_trace.py sleeps 2 ms in its bm:next: most of the idle time
    assert d["idle_by_phase_ns"] == {"fetch": 5132672, "next": 7395340,
                                     "enqueue": 820560,
                                     "between steps": 38389}
    assert d["idle_gaps"][:4] == [("next", 4382548), ("next", 3399089),
                                  ("next", 3170929), ("fetch", 975481)]
    assert sum(d["idle_by_phase_ns"].values()) == \
        d["window_ns"] - d["busy_ns"]
    assert r["device_ops"][0][0] == "fusion:add_add_fusion"
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.951, abs=1e-3)


def test_recorded_four_chip_step():
    """Three executions (two periods) of tfm_base_train_dp2tp2's step on
    the first two of four v5e chips, cut from the cell's traced stretch
    by trim_trace.py: flash under shard_map, synchronous tp all-reduces,
    async collective-permutes, and the harness's own annotations."""
    trace = tr.read_xplane(os.path.join(DATA, "dp2tp2_3runs.xplane.pb.gz"))
    assert sorted(trace["devices"]) == ["/device:TPU:0", "/device:TPU:1"]
    r = tr.reduce(trace)
    assert r["first"] == "/device:TPU:0"
    d0, d1 = (r["devices"][p] for p in sorted(r["devices"]))
    assert d0["module"].startswith("jit_step(") and d0["steps"] == 2
    assert (d0["window_ns"], d0["busy_ns"]) == (227946496, 213515494)
    assert (d1["window_ns"], d1["busy_ns"]) == (228013079, 213312059)
    # without the compiled step's text the matmul fusions are `fusion`
    assert d0["category_ns"] == {
        "copy": 15136739, "other": 1791631, "collective": 65470644,
        "fusion": 105951045, "mosaic": 24979262}
    # per step 48.9 ms of collectives in flight, 32.8 ms with no other
    # operation beside them: the synchronous all-reduces, and the part
    # of the async collective-permutes that compute does not cover
    assert d0["collective_ns"] == 97835274
    assert d0["collective_exposed_ns"] == 65652229
    assert tr.per_step_ms(r, "collective_exposed_ns") == \
        pytest.approx(32.826, abs=1e-3)
    # only the first chip's plane has an `Async XLA Ops` line: on the
    # second, collectives are the operations on the op line alone
    assert d1["collective_ns"] == d1["collective_exposed_ns"] == 65139468
    # a program over four chips is launched and seen done four times a
    # step: the first launch and the last completion bound the shift,
    # loosely (1.8 ms either way)
    assert d0["host_offset_ns"] == -39034.5
    assert d0["host_offset_slack_ns"] == 1785203.5
    assert d0["idle_by_phase_ns"] == {"between steps": 25776,
                                      "fetch": 4697003, "next": 177070,
                                      "enqueue": 9531153}
    assert sum(d0["idle_by_phase_ns"].values()) == \
        d0["window_ns"] - d0["busy_ns"]
    # averaged over the chips in the trace
    assert r["busy_s"] == pytest.approx(0.2134137765)
    assert r["window_s"] == pytest.approx(0.2279797875)
    assert [k for k, _ in r["device_ops"][:3]] == [
        "fusion:fusion", "collective:all-reduce", "mosaic:shard_map"]
    assert r["idle_by_phase"][0] == ["enqueue", pytest.approx(0.009531153)]
