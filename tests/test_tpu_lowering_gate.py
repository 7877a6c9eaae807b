"""Full-model TPU compile gate (tools/tpu_lowering_check.py).

The kernel-level tests (test_pallas_kernels.py, test_chip_compile.py)
check the kernels in isolation; this checks COMPLETE programs
(tools/gate_programs.py: IR build -> transpiles -> autodiff ->
optimizer -> jit) compiled by the chip's own compiler for a described
v5e.  A subset runs here; tools/ci.sh runs the full sweep.
"""

import pytest


@pytest.mark.parametrize("workload", [
    "transformer_train",       # the one that crashed on first chip run;
    #                            also chip_smoke.py's train step
    "deepfm_train",
    "resnet50_infer_int8",     # int8 dot_general path
    # ISSUE 5: s8-in convs + fused requantize epilogues — the
    # interlayer lowering surface
    "resnet50_infer_int8_interlayer",
    # ISSUE 7: the paged flash-decode step (scalar-prefetch block
    # tables + head-packed page blocks); ci.sh step 7 sweeps the
    # remaining variant flags (int8kv, bf16, d128)
    "llm_decode_d64_hp2",
    # ISSUE 8: the gspmd-sharded train step — one jit with in/out
    # NamedShardings over the dp x tp mesh, flash kernels under
    # shard_map (per-shard B/dp x H/tp block shapes the single-device
    # lowering never sees)
    "transformer_train_gspmd",
    # ISSUE 14: the tp-sharded serving-inference graph (column-
    # parallel weights + SPMD inter-layer gathers) and the disagg
    # decode graph (handoff-fragmented block tables)
    "serving_tp_sharded",
    "llm_decode_disagg",
])
def test_bench_workload_lowers_for_tpu(chip_gate, workload):
    ok, detail, _ = chip_gate.check_workload(
        workload, chip_gate._workloads()[workload])
    assert ok, detail
    if workload in chip_gate.NO_HEAD_LAYOUT_COPIES:
        # flash attention takes the projections' [B, T, H*d] as it is
        # (ISSUE 31): 12 kernels, and no head split or merge around them
        assert detail["head_layout_copies"] == 0
        assert detail["tpu_custom_calls"] == 12


def test_evabyte_step_holds_each_eva_kernel_once_an_op(chip_gate):
    """EvaByte's four layers at the published head size, window and
    chunk, two windows, narrow (ISSUE 55): under RecomputeOptimizer a
    segment's backward takes the saved summaries, Out and LSE, so the
    compiled step holds each of the six Mosaic calls of an EVA mixer
    (the summariser, a window's causal flash, the staircase; forward
    and backward) once a layer and not again in the replay; q and k
    turn where they lie; and no float array of [.., 4096, 2048] or, a
    window a row, [.., 2048, 2048] (a window's scores) or [.., 4096,
    256] (the chunk keys') exists."""
    workload = "evabyte_train_tiny"
    assert workload in chip_gate.EVA_KERNELS_AN_OP
    assert workload in chip_gate.ROTARY_KERNEL
    ok, detail, _ = chip_gate.check_workload(
        workload, chip_gate._workloads()[workload])
    assert ok, detail
    assert detail["eva_ops"] == 4
    assert detail["eva_score_arrays"] == []
    assert detail["kernel_calls"] == dict(
        {k: 4 for k in chip_gate.EVA_KERNELS}, pt_rotary=24)
    assert detail["rotary_kernel_ops"] == 8
    assert detail["tpu_custom_calls"] == 48


@pytest.mark.parametrize("workload,flash_ops", [
    ("xing4_train_tiny", 5), ("ouro_train_tiny", 24),
    ("dsv2_train_tiny", 5), ("granite_train_tiny", 1),
    ("ling3_train_tiny", 1), ("lfm2_train_tiny", 1),
    ("solar_open2_train_tiny", 1), ("mellum2_train_tiny", 1)])
def test_recompute_step_holds_one_forward_kernel_a_flash_op(
        chip_gate, workload, flash_ops):
    """The eight cells that train under RecomputeOptimizer, at their
    depth and head sizes, narrow and short: a segment's backward takes
    the forward's Out and LSE (ISSUE 33), so the compiled step holds
    one `pt_flash_fwd` a flash op and not a second in every segment's
    replay (10 and 48 before).  The gate fails the workload
    otherwise."""
    assert workload in chip_gate.ONE_FLASH_FWD_AN_OP
    ok, detail, _ = chip_gate.check_workload(
        workload, chip_gate._workloads()[workload])
    assert ok, detail
    assert detail["flash_ops"] == flash_ops
    assert detail["kernel_calls"]["pt_flash_fwd"] == flash_ops
    assert detail["kernel_calls"]["pt_flash_bwd_dkv"] == flash_ops
    # the rotary ops whose X is whole lane tiles of heads (every q and
    # k; not the latent attention's one shared key a token) turn it
    # where it lies (ISSUE 54): pt_rotary in the forward pass, in the
    # segment's replay, and once more as the op's own backward
    rotary_ops = {"xing4_train_tiny": 5, "ouro_train_tiny": 48,
                  "dsv2_train_tiny": 5, "ling3_train_tiny": 1,
                  "lfm2_train_tiny": 2, "mellum2_train_tiny": 8}
    assert (workload in chip_gate.ROTARY_KERNEL) == (workload in rotary_ops)
    if workload in rotary_ops:
        assert detail["rotary_kernel_ops"] == rotary_ops[workload]
        assert detail["kernel_calls"]["pt_rotary"] \
            == 3 * rotary_ops[workload]
    else:
        assert "pt_rotary" not in detail["kernel_calls"]
    if workload == "granite_train_tiny":
        # one period of granite-4.0-h-micro at its head sizes, state
        # size and chunk (ISSUE 38): nine scans, each forward kernel
        # ONCE (a segment binds the saved Y and chunk-start states on
        # the op it replays: not 18, never 27) and each backward once;
        # the one attention layer reads 2 KV heads from 4 query heads
        assert workload in chip_gate.ONE_SSD_FWD_AN_OP
        assert detail["ssd_ops"] == 9
        assert detail["kernel_calls"]["pt_ssd_fwd"] == 9
        assert detail["kernel_calls"]["pt_ssd_bwd"] == 9
        # and nine short convolutions through their kernels (ISSUE 42):
        # the forward pass, its segment's replay (the op keeps no
        # output) and one backward each, no float32 pad of X left
        assert workload in chip_gate.CONV1D_KERNELS
        assert detail["conv1d_ops"] == 9
        assert detail["kernel_calls"]["pt_conv1d_fwd"] == 18
        assert detail["kernel_calls"]["pt_conv1d_bwd"] == 9
        assert detail["conv_scope_pads"] == 0
        assert detail["tpu_custom_calls"] == 47
    if workload == "ling3_train_tiny":
        # the dense layer and one period of ling-3.0-flash-vl at its
        # head sizes, chunking, router and expert width (ISSUE 41): six
        # KDA scans, each forward kernel ONCE (a segment binds the
        # saved O and block-start states on the op it replays: not 12,
        # never 18) and each backward once; one gated latent-attention
        # layer; six expert layers' grouped matmuls at width 768
        assert workload in chip_gate.ONE_KDA_FWD_AN_OP
        assert detail["kda_ops"] == 6
        assert detail["kernel_calls"]["pt_kda_fwd"] == 6
        assert detail["kernel_calls"]["pt_kda_bwd"] == 6
        # three short convolutions a KDA layer (ISSUE 42)
        assert workload in chip_gate.CONV1D_KERNELS
        assert detail["conv1d_ops"] == 18
        assert detail["kernel_calls"]["pt_conv1d_fwd"] == 36
        assert detail["kernel_calls"]["pt_conv1d_bwd"] == 18
        assert detail["conv_scope_pads"] == 0
        assert [detail["kernel_calls"][k] for k in (
            "pt_gmm_fwd", "pt_gmm_bwd_dx", "pt_gmm_bwd_dw")] == [36, 18, 18]
        # and each layer's combine by token through its kernel (ISSUE
        # 43): the forward's and d x; the replay's is dead code (a
        # layer's output is the last thing its segment makes)
        assert workload in chip_gate.MOE_COMBINE_KERNEL
        assert detail["moe_ops"] == 6
        assert detail["kernel_calls"]["pt_moe_combine"] == 12
    if workload == "mellum2_train_tiny":
        # one period of mellum2-12b-a2.5b as an expert-parallel rank
        # holds it, at its head size, group of 8, window (1,024 of
        # 2,048 tokens), YaRN numbers, router (64 outputs) and expert
        # width (ISSUE 53): three window layers, each pt_flash_win_fwd
        # ONCE (a segment binds the saved Out and LSE on the op it
        # replays) and the one-sweep pt_flash_win_bwd_dkv once, through
        # Mosaic with their band grid (2 steps a q block of 1,024 rows)
        # and the loops that zero and write the whole dq; one full layer under the names the other
        # cells' calls carry; four expert layers' grouped matmuls at
        # width 896 over K 2,304 and their combines
        assert detail["window_flash_ops"] == 3
        assert detail["kernel_calls"]["pt_flash_win_fwd"] == 3
        assert detail["kernel_calls"]["pt_flash_win_bwd_dkv"] == 3
        assert "pt_flash_win_bwd_dq" not in detail["kernel_calls"]
        assert [detail["kernel_calls"][k] for k in (
            "pt_gmm_fwd", "pt_gmm_bwd_dx", "pt_gmm_bwd_dw")] == [24, 12, 12]
        assert workload in chip_gate.MOE_COMBINE_KERNEL
        assert detail["moe_ops"] == 4
        assert detail["kernel_calls"]["pt_moe_combine"] == 8
        assert detail["kernel_calls"]["pt_row_buffer"] == 20
        # 24 pt_rotary among them since ISSUE 54
        assert detail["tpu_custom_calls"] == 108
        from paddle_tpu import framework

        ops = framework.default_main_program().global_block().ops
        assert [op.attrs["window"] for op in ops
                if op.type == "flash_attention"] == [1024, 1024, 1024, 0]
    if workload == "solar_open2_train_tiny":
        # one period of solar-open2-250b as a rank holds it, at its
        # head sizes, chunking, taps, router (320 outputs) and expert
        # width (ISSUE 49): one gated attention layer at 2 query heads
        # on ONE KV head of 128; three KDA scans on the path that is
        # exact for an unbounded decay (its levels' sublane rolls
        # through Mosaic), each forward kernel ONCE and each backward
        # once; nine short convolutions; four expert layers' grouped
        # matmuls at width 1,280 and their combines
        assert workload in chip_gate.ONE_KDA_FWD_AN_OP
        assert detail["kda_ops"] == 3
        assert detail["kernel_calls"]["pt_kda_fwd"] == 3
        assert detail["kernel_calls"]["pt_kda_bwd"] == 3
        assert workload in chip_gate.CONV1D_KERNELS
        assert detail["conv1d_ops"] == 9
        assert detail["kernel_calls"]["pt_conv1d_fwd"] == 18
        assert detail["kernel_calls"]["pt_conv1d_bwd"] == 9
        assert detail["conv_scope_pads"] == 0
        assert [detail["kernel_calls"][k] for k in (
            "pt_gmm_fwd", "pt_gmm_bwd_dx", "pt_gmm_bwd_dw")] == [24, 12, 12]
        assert workload in chip_gate.MOE_COMBINE_KERNEL
        assert detail["moe_ops"] == 4
        assert detail["kernel_calls"]["pt_moe_combine"] == 8
        assert detail["tpu_custom_calls"] == 111
        from paddle_tpu import framework

        ops = framework.default_main_program().global_block().ops
        assert {op.attrs["decay"] for op in ops
                if op.type == "kda_scan"} == {"unbounded"}
    if workload == "lfm2_train_tiny":
        # the cell's five layers of lfm2-24b-a2b at its head size, taps,
        # router and expert width (ISSUE 45): four gated convolutions
        # through the kernels pt_conv1d_* (the forward pass, its
        # segment's replay and one backward each) with nothing of the
        # XLA composition left in their scope: no pad, no concatenate,
        # no split of the projection or array of the step's tokens
        assert workload in chip_gate.CONV1D_KERNELS
        assert workload in chip_gate.GATED_CONV_IN_PLACE
        assert detail["conv1d_ops"] == 4
        assert detail["kernel_calls"]["pt_conv1d_fwd"] == 8
        assert detail["kernel_calls"]["pt_conv1d_bwd"] == 4
        assert detail["conv_scope_pads"] == 0
        assert detail["gated_conv_copies"] == 0
        # the attention layer reads 2 rotated KV heads from 4 query
        # heads; four expert layers of 16 held experts at width 1,536
        assert [detail["kernel_calls"][k] for k in (
            "pt_gmm_fwd", "pt_gmm_bwd_dx", "pt_gmm_bwd_dw")] == [24, 12, 12]
        assert workload in chip_gate.MOE_COMBINE_KERNEL
        assert detail["moe_ops"] == 4
        assert detail["kernel_calls"]["pt_moe_combine"] == 8
        assert detail["kernel_calls"]["pt_row_buffer"] == 20
        # 6 pt_rotary among them since ISSUE 54
        assert detail["tpu_custom_calls"] == 96
    if workload == "dsv2_train_tiny":
        # four expert layers at the published expert width, 1,408 =
        # 11 x 128: the grouped matmuls compile with that axis whole
        # (three forward and their replay, three and three backward;
        # since ISSUE 50 the down projection, d W_down and d act form
        # SwiGLU and its gradient themselves)
        assert [detail["kernel_calls"][k] for k in (
            "pt_gmm_fwd", "pt_gmm_bwd_dx", "pt_gmm_bwd_dw")] == [24, 12, 12]
        # and nothing else of the step by padded row stands outside the
        # loops over the live rows (ISSUE 37): 14 tiles of 256 here;
        # the two gathers by row and d x's sum are the loops left,
        # over five unwritten buffers a layer where ten were
        assert workload in chip_gate.ROW_WORK_IN_LOOPS
        assert detail["rows_outside_loops"] == 0
        assert detail["kernel_calls"]["pt_row_buffer"] == 20
        assert workload in chip_gate.MOE_COMBINE_KERNEL
        assert detail["moe_ops"] == 4
        assert detail["kernel_calls"]["pt_moe_combine"] == 8
    if workload == "xing4_train_tiny":
        # the replay's combine too: the stream mix after the experts
        # reads their output in its backward
        assert workload in chip_gate.MOE_COMBINE_KERNEL
        assert detail["moe_ops"] == 4
        assert detail["kernel_calls"]["pt_moe_combine"] == 12
        # the ten hyper-connections through the kernels of
        # ops/pallas_mhc.py (ISSUE 52): mhc_pre's forward in the
        # forward pass and in each segment's replay, mhc_post's too but
        # a segment's last, each backward once; and under the scope
        # pt_mhc XLA makes no stream-sized array (the composition's
        # casts, broadcast products and copies)
        assert workload in chip_gate.MHC_STREAMS_IN_KERNELS
        assert {k: detail["kernel_calls"][k]
                for k in chip_gate.MHC_KERNEL_CALLS} == {
            "pt_mhc_pre_fwd": 20, "pt_mhc_post_fwd": 15,
            "pt_mhc_pre_bwd": 10, "pt_mhc_post_bwd": 10}
        assert detail["mhc_stream_moves"] == []
        # 15 pt_rotary among them since ISSUE 54
        assert detail["tpu_custom_calls"] == 160
        assert chip_gate.STEP_BYTES_MAX["xing4_train"] == 12_753_077_248


def test_mhc_stream_moves_reads_stream_sized_arrays_under_the_scope():
    """The reader itself, on text: a fusion, copy, convert or transpose
    under pt_mhc that yields an array of a stream's elements, alone or
    in a tuple; not the kernels, not a coefficient-sized array, not
    another op's scope."""
    from tools.tpu_lowering_check import mhc_stream_moves

    scope = 'metadata={op_name="jit(step)/pt_forward.mhc_post/pt_mhc/mul"}'
    text = """
  %fusion.1 = f32[1,4,256,256]{3,2,1,0} fusion(%a), kind=kLoop, calls=%f, SCOPE
  %copy.2 = bf16[1,256,256]{2,1,0} copy(%a), SCOPE
  %fusion.3 = (f32[1,4,256]{2,1,0}, bf16[4,65536]{1,0}) fusion(%a), kind=kLoop, calls=%f, SCOPE
  %fusion.4 = f32[1,256,20]{2,1,0} fusion(%a), kind=kLoop, calls=%f, SCOPE
  %transpose.5 = f32[1,20,256]{2,1,0} transpose(%a), dimensions={0,2,1}, SCOPE
  %pt_mhc_post_fwd.6 = bf16[1,4,256,256]{3,2,1,0} custom-call(%a), custom_call_target="tpu_custom_call", SCOPE
  %fusion.7 = f32[1,4,256,256]{3,2,1,0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step)/pt_forward.sum/add"}
""".replace("SCOPE", scope)
    found = mhc_stream_moves(text, (4 * 256 * 256, 256 * 256))
    assert [f.split()[0] for f in found] == ["fusion", "copy", "fusion"]


def test_kernel_calls_counts_mosaic_calls_by_kernel_name():
    """The reader itself, on text: only Mosaic calls, the compiler's
    numbering stripped."""
    from tools.tpu_lowering_check import kernel_calls

    text = """
ENTRY %main {
  %pt_flash_fwd = (bf16[1,8]{1,0}, f32[1,8]{1,0}) custom-call(%a), custom_call_target="tpu_custom_call"
  %pt_flash_fwd.7 = (bf16[1,8]{1,0}, f32[1,8]{1,0}) custom-call(%a), custom_call_target="tpu_custom_call"
  %pt_flash_bwd_dkv.12.1 = bf16[1,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call"
  %cholesky.3 = f32[8,8]{1,0} custom-call(%b), custom_call_target="Cholesky"
  ROOT %pt_gmm_fwd.2 = bf16[1,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call"
}
"""
    assert kernel_calls(text) == {"pt_flash_fwd": 2, "pt_flash_bwd_dkv": 1,
                                  "pt_gmm_fwd": 1}


def test_rows_outside_loops_counts_float_row_arrays_not_in_a_while():
    """The reader itself, on text: fusions, gathers and copies of a
    float [rows, width] array count unless a `while` body, or a
    computation one calls, holds them; kernels, loops, index vectors
    and other row counts do not."""
    from tools.tpu_lowering_check import rows_outside_loops

    text = """HloModule m
%fused_computation.1 (p: bf16[512,64]) -> bf16[512,64] {
  ROOT %g = bf16[512,64]{1,0} gather(%p, %i), offset_dims={1}
}

%helper.2 (p: bf16[512,64]) -> bf16[512,64] {
  ROOT %fusion.9 = bf16[512,64]{1,0} fusion(%p), kind=kLoop, calls=%fused_computation.1
}

%body.3 (t: (s32[], bf16[512,64])) -> (s32[], bf16[512,64]) {
  %fusion.4 = bf16[512,64]{1,0:T(8,128)(2,1)} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %call.5 = bf16[512,64]{1,0} call(%fusion.4), to_apply=%helper.2
  ROOT %tuple = (s32[], bf16[512,64]{1,0}) tuple(%i, %call.5)
}

ENTRY %main (a: bf16[64,64]) -> bf16[512,64] {
  %pt_gmm_fwd.1 = bf16[512,64]{1,0} custom-call(%a), custom_call_target="tpu_custom_call"
  %while.6 = (s32[], bf16[512,64]{1,0}) while(%init), condition=%cond.7, body=%body.3
  %fusion.8 = bf16[512,64]{1,0:T(8,128)(2,1)} fusion(%pt_gmm_fwd.1), kind=kLoop, calls=%fused_computation.1
  %fusion.10 = (f32[512,64]{1,0}, f32[512]{0}) fusion(%fusion.8), kind=kLoop, calls=%fused_computation.1
  %copy.11 = f32[512,64]{0,1} copy(%gte)
  %fusion.12 = s32[512,1]{0,1} fusion(%idx), kind=kLoop, calls=%fused_computation.1
  %fusion.13 = bf16[256,64]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.1
  ROOT %gather.14 = bf16[512,64]{1,0} gather(%copy.11, %idx), offset_dims={1}
}
"""
    assert rows_outside_loops(text, 512) == 4       # 8, 10, 11, 14
    assert rows_outside_loops(text, 256) == 1
    assert rows_outside_loops(text, 64) == 0


def test_head_layout_copies_counts_rank4_float_copies_of_the_entry():
    """The reader itself, on text: only ENTRY, only `copy`, only rank-4
    float arrays (a head split is [B, T, H, d] <-> [B, H, T, d])."""
    from tools.tpu_lowering_check import head_layout_copies

    text = """HloModule m
%fused (p: bf16[64,8,512,64]) -> bf16[64,8,512,64] {
  %c = bf16[64,8,512,64]{3,2,1,0} copy(%p)
}

ENTRY %main (a: bf16[64,512,512]) -> bf16[64,512,512] {
  %copy.1 = bf16[64,8,512,64]{3,2,1,0:T(8,128)(2,1)} copy(%bitcast.1), metadata={}
  %copy.2 = bf16[64,512,8,64]{1,0,3,2:T(8,128)(2,1)S(1)} copy(%copy.1)
  %copy.3 = bf16[512,512]{0,1:T(8,128)(2,1)} copy(%w)
  %copy.4 = s32[1,4,4,128]{3,1,2,0:T(4,128)} copy(%idx)
  %copy.5 = f32[64,512,512]{1,2,0:T(8,128)} copy(%x)
  %t = bf16[64,8,512,64]{3,2,1,0} transpose(%y), dimensions={0,2,1,3}
}
"""
    assert head_layout_copies(text) == 2


@pytest.mark.parametrize("which,causal", [
    ("ring", False), ("ring", True),
    ("ulysses", False), ("ulysses", True),
])
def test_sequence_parallel_flash_lowers_for_tpu(which, causal):
    """The sp paths run the Pallas kernel on PER-CHUNK shapes inside
    shard_map — different block shapes than the single-chip bench, so
    they get their own Mosaic legality check.  The described v5e:2x2
    has four devices and sp is eight here, so this one stays a
    jax.export cross-lowering over an AbstractMesh: Mosaic's lowering
    rules run, the TPU compiler does not."""
    import jax
    import jax.numpy as jnp
    from jax import export
    from jax.sharding import AbstractMesh

    from paddle_tpu.parallel.ring_attention import ring_attention
    from paddle_tpu.parallel.ulysses import ulysses_attention

    fn = ring_attention if which == "ring" else ulysses_attention
    mesh = AbstractMesh((8,), ("sp",))
    q = jnp.zeros((2, 4096, 8, 64), jnp.bfloat16)

    def step(q, k, v):
        def loss(q, k, v):
            return fn(q, k, v, mesh=mesh, axis="sp", causal=causal,
                      impl="flash").astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    export.export(jax.jit(step), platforms=("tpu",))(q, q, q)


# -- the grouped matmuls at the cells' real shapes (ISSUE 40, 50) -------------

# The cells' expert layers: the pairs a step routes at the worst case,
# hidden width, expert width, experts held.  Tiles of 256 rows, bf16.
GMM_CELLS = {"dsv2": (49152, 2048, 1408, 8), "xing4": (16384, 3584, 1024, 8),
             # ISSUE 41: 4,096 tokens x 8 experts a token, K 2,560,
             # expert width 768 = 6 x 128
             "ling3": (32768, 2560, 768, 8),
             # ISSUE 45: 8,192 tokens x 4, width 1,536 = 12 x 128
             "lfm2": (32768, 2048, 1536, 16),
             # ISSUE 49: 8,192 tokens x 8 at hidden 4,096, width 1,280
             "solar_open2": (65536, 4096, 1280, 8),
             # ISSUE 53: 16,384 tokens x 8, K 2,304 = 18 x 128, width
             # 896 = 7 x 128, 16 held
             "mellum2": (131072, 2304, 896, 16)}
GMM_TM = 256
# The six grouped-matmul call forms of an expert layer's forward and
# backward (moe_experts' _routed_fwd / _routed_bwd): the kernel
# (pallas_gmm._BLOCKS), rhs transposed, (k, n) of hidden h and width w,
# and how many run a step (the recompute segment replays the forward;
# up is gate's shape).  Since ISSUE 50 SwiGLU goes into the down
# projection and d W_down, and its gradient comes out of d act.
GMM_CALLS = {
    "fwd_gate_up": ("gmm", False, lambda h, w: (h, w), 4),
    "fwd_down": ("gmm_swiglu", False, lambda h, w: (w, h), 2),
    "dx_down": ("gmm_swiglu_grad", True, lambda h, w: (h, w), 1),
    "dx_gate_up": ("gmm", True, lambda h, w: (w, h), 2),
    "dw_down": ("tgmm_swiglu", False, lambda h, w: (w, h), 1),
    "dw_gate_up": ("tgmm", False, lambda h, w: (h, w), 2)}


def _gmm_call(cell, call):
    """One grouped-matmul call at the cell's real shapes: fn and avals
    to trace or compile, the kernel, its k and n, and how many such
    calls a step makes."""
    import types

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_gmm import gmm_pallas, tgmm_pallas

    rows, h, w, held = GMM_CELLS[cell]
    kernel, transpose_rhs, kn, times = GMM_CALLS[call]
    k, n = kn(h, w)
    m = (rows // GMM_TM + held) * GMM_TM

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    maps = (sds((m // GMM_TM,), jnp.int32), sds((1,), jnp.int32))
    # SwiGLU's pair (hg, hu) where the form takes one
    lhs = (sds((m, k)),) * 2 if kernel in ("gmm_swiglu", "tgmm_swiglu") \
        else sds((m, k))
    if kernel.startswith("tgmm"):
        def fn(x, g, tg, na):
            return tgmm_pallas(x, g, tg, na, GMM_TM, held)
        operands = (lhs, sds((m, n)))
    else:
        def fn(x, wt, gated, tg, na):
            return gmm_pallas(x, wt, tg, na, GMM_TM,
                              transpose_rhs=transpose_rhs, gated=gated)
        operands = (lhs, sds((held, n, k) if transpose_rhs else (held, k, n)),
                    (sds((m, n)),) * 2 if kernel == "gmm_swiglu_grad"
                    else ())
    return types.SimpleNamespace(fn=fn, avals=operands + maps,
                                 kernel=kernel, k=k, n=n, times=times)


def _grid_steps_a_live_tile(call):
    """From the call's jaxpr: the product of its grid's static axes,
    what ONE more live row tile adds to the grid (the row-tile axis is
    the run-time n_active)."""
    import jax

    def pallas_calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from pallas_calls(sub)

    eqn, = pallas_calls(jax.make_jaxpr(call.fn)(*call.avals).jaxpr)
    mapping = eqn.params["grid_mapping"]
    assert mapping.num_dynamic_grid_bounds == 1
    steps = 1
    for axis in mapping.grid:
        steps *= axis if isinstance(axis, int) else 1
    return steps


@pytest.mark.parametrize("call", sorted(GMM_CALLS))
@pytest.mark.parametrize("cell", sorted(GMM_CELLS))
def test_grouped_matmul_compiles_at_the_cells_shapes(chip_gate, cell, call):
    """Every call shape of an expert layer at real M, lowered and
    compiled by the chip's own compiler under the VMEM limit the call
    passes: blocks the rule reckons to fit and Mosaic refuses fail
    here, not on the chip."""
    c = _gmm_call(cell, call)
    exe = chip_gate.compile_for_chip(c.fn, c.avals)
    assert exe.as_text().count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("call", sorted(GMM_CALLS))
@pytest.mark.parametrize("cell", sorted(GMM_CELLS))
def test_grouped_matmul_blocks_fit_the_limit_the_call_passes(cell, call):
    """Pure Python: the footprint `_tiles` reckons for the blocks it
    chose (two buffers a block, the float32 accumulator) is inside the
    budget and under what the call passes as vmem_limit_bytes, which is
    Mosaic's default scope or more and well inside the core's 128 MiB;
    the blocks are ones the axes admit, and none is 128 wide along
    1,408."""
    from paddle_tpu.ops import pallas_gmm as pg
    from paddle_tpu.ops.pallas_kernels import _MOSAIC_SCOPED_VMEM

    c = _gmm_call(cell, call)
    tn, tk = pg._tiles(c.kernel, c.k, c.n, GMM_TM, 2)
    assert tn in pg._blocks(c.n) and tk in pg._blocks(c.k)
    need = pg._vmem_bytes(c.kernel, GMM_TM, tk, tn, 2)
    assert need <= pg._VMEM_BUDGET
    assert max(need + 1, _MOSAIC_SCOPED_VMEM) <= pg._vmem_limit(need) \
        <= 100 << 20
    for dim, block in ((c.n, tn), (c.k, tk)):
        assert dim != 1408 or block == 1408


# (tn, tk) of every call form at every cell's shapes, as _tiles gives
# them under the 40 MiB budget with each form's extra blocks counted
# (ISSUE 50): whole matrices but where the float32 [W, C] accumulator
# of a weight gradient passes it (solar_open2).  A change to the
# budget, or to what _vmem_bytes counts, shows here which cell's grid
# it alters.
GMM_TILES = {
    "dsv2": {"fwd_gate_up": (1408, 2048), "fwd_down": (2048, 1408),
             "dx_down": (1408, 2048), "dx_gate_up": (2048, 1408),
             "dw_down": (2048, 1408), "dw_gate_up": (1408, 2048)},
    "xing4": {"fwd_gate_up": (1024, 3584), "fwd_down": (3584, 1024),
              "dx_down": (1024, 3584), "dx_gate_up": (3584, 1024),
              "dw_down": (3584, 1024), "dw_gate_up": (1024, 3584)},
    "ling3": {"fwd_gate_up": (768, 2560), "fwd_down": (2560, 768),
              "dx_down": (768, 2560), "dx_gate_up": (2560, 768),
              "dw_down": (2560, 768), "dw_gate_up": (768, 2560)},
    "lfm2": {"fwd_gate_up": (1536, 2048), "fwd_down": (2048, 1536),
             "dx_down": (1536, 2048), "dx_gate_up": (2048, 1536),
             "dw_down": (2048, 1536), "dw_gate_up": (1536, 2048)},
    "solar_open2": {"fwd_gate_up": (1280, 4096), "fwd_down": (4096, 1280),
                    "dx_down": (1280, 4096), "dx_gate_up": (4096, 1280),
                    "dw_down": (4096, 640), "dw_gate_up": (1280, 2048)},
    "mellum2": {"fwd_gate_up": (896, 2304), "fwd_down": (2304, 896),
                "dx_down": (896, 2304), "dx_gate_up": (2304, 896),
                "dw_down": (2304, 896), "dw_gate_up": (896, 2304)}}


@pytest.mark.parametrize("call", sorted(GMM_CALLS))
@pytest.mark.parametrize("cell", sorted(GMM_CELLS))
def test_tiles_at_the_cells_shapes(cell, call):
    from paddle_tpu.ops import pallas_gmm as pg

    c = _gmm_call(cell, call)
    tn, tk = pg._tiles(c.kernel, c.k, c.n, GMM_TM, 2)
    assert (tn, tk) == GMM_TILES[cell][call]
    assert pg._vmem_bytes(c.kernel, GMM_TM, tk, tn, 2) <= pg._VMEM_BUDGET
    # the one block larger along either axis would not have fitted, or
    # there is none
    for wider in ((2 * tn, tk), (tn, 2 * tk)):
        if wider[0] <= c.n and wider[1] <= c.k:
            assert pg._vmem_bytes(c.kernel, GMM_TM, wider[1], wider[0], 2) \
                > pg._VMEM_BUDGET


@pytest.mark.parametrize("cell,most", [("dsv2", 38), ("xing4", 89),
                                       ("ling3", 12), ("lfm2", 12),
                                       ("solar_open2", 15),
                                       ("mellum2", 12)])
def test_a_live_tile_layer_costs_few_grid_steps(cell, most):
    """What one more live row tile of one expert layer adds to a
    step's grids, summed over the layer's twelve calls, from their
    jaxprs.  The rule gives 12 where every matrix is a block (dsv2,
    xing4, ling3, lfm2, mellum2) and 15 in solar_open2 (d W_down, d W_gate and
    d W_up take halves).  The bounds:
    dsv2 38, what 1,408 whole over blocks of 512 and 1,024 costs (4 +
    4 + 2 forward, twice; 4 + 2 + 2 for dx; 2 + 4 + 4 for dw), where
    128-wide blocks along 1,408 made it 418; xing4 the 89 of its
    former 1024x512 / 896x512 blocks (7 a call at [3584, 1024], 8 at
    [1024, 3584]); ling3 and lfm2 the 12 of whole matrices ([2560,
    768] is 3.9 MB in bf16: a block); solar_open2 its 15, the same
    before and after ISSUE 50.  A rule that falls back to narrow
    blocks fails here."""
    steps = 0
    for name in GMM_CALLS:
        call = _gmm_call(cell, name)
        steps += call.times * _grid_steps_a_live_tile(call)
    assert steps <= most
