"""Round-trip property test (ISSUE 15 satellite): programs the
lowering-gate builders (tools/gate_programs.py) construct verify green, serialize through
to_bytes/parse_from_bytes with an unchanged ``program_fingerprint``,
and re-verify green after each applicable transpiler pass.

The suite runs with ``ir_verify`` forced "on" (tests/conftest.py), so
each builder's internal transpiles are ALSO verify-bracketed while it
builds — the explicit checks below add the serialization-stability
property and the named per-pass chain.  tools/verifier_sweep.py runs
the full gate-workload list under level "full" in ci.sh.
"""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import framework, optimizer
from paddle_tpu.analysis import check_shapes, check_sharding, verify
from paddle_tpu.core.compiler import program_fingerprint
from paddle_tpu.core.program import Program
from paddle_tpu.parallel.gspmd import MeshPlan


def _errors(diags):
    # warnings (orphan-var: fuse passes legally strand erased
    # intermediates' VarDescs) are allowed; errors are not
    return [d for d in diags if d.severity == "error"]


def _roundtrip_stable(program):
    fp = program_fingerprint(program)
    restored = Program.parse_from_bytes(program.to_bytes())
    assert program_fingerprint(restored) == fp
    assert _errors(verify(restored)) == []
    return fp


# tiny shapes: the property under test is IR structure, not perf —
# the builders tools/tpu_lowering_check.py compiles at real size.
# Each entry builds and returns the program to check, or None for the
# default main program.
_BUILDERS = {
    "transformer_train": lambda b: b._build_transformer_train(2, 64),
    "transformer_train_fusedadam": lambda b:
        b._build_transformer_train(2, 64, fused_adam=True),
    "transformer_train_fcep": lambda b:
        b._build_transformer_train(2, 64, fc_epilogue=True),
    "transformer_train_gspmd": lambda b:
        b._build_transformer_train(2, 64, gspmd=True, tp=2),
    "deepfm_train": lambda b: b._build_deepfm_train(64),
    # ops under framework.name_scope carry their scope through bytes
    "ouro_train": lambda b: b._build_ouro_train(
        2, 32, hidden_size=64, num_attention_heads=4, head_dim=16,
        num_key_value_heads=4, intermediate_size=160, vocab_size=256,
        num_hidden_layers=2),
    # a balance loss under name_scope that crosses recompute segments,
    # moe_route's optional Bias input left unbound
    "dsv2_train": lambda b: b._build_dsv2_train(
        2, 32, hidden_size=64, num_attention_heads=4, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        intermediate_size=160, moe_intermediate_size=32,
        n_routed_experts=4, held_experts=[0, 1, 2, 3], vocab_size=256,
        num_hidden_layers=3),
    "bert_train": lambda b: b._build_bert_train(1, 128),
    "longctx_train": lambda b: b._build_longctx_train(1, 2, 512, 64),
    "serving_tp_sharded": lambda b: b._build_serving_tp_sharded(tp=2),
    "resnet50_train": lambda b: b._build_resnet50_train(2),
    "resnet50_train_s2d": lambda b:
        b._build_resnet50_train(2, s2d=True),
    "resnet50_train_convep": lambda b:
        b._build_resnet50_train(2, conv_epilogue=True),
    "resnet50_train_convbnstats": lambda b:
        b._build_resnet50_train(2, conv_bn_stats=True),
    # the int8 programs are transpiled clones, which the builder returns
    "resnet50_infer_int8": lambda b:
        b._build_resnet50_infer_int8(2)[-1],
    "resnet50_infer_int8_interlayer": lambda b:
        b._build_resnet50_infer_int8(2, int8_activations=True)[-1],
}


@pytest.mark.parametrize("name", sorted(_BUILDERS))
def test_bench_builder_programs_roundtrip(name):
    from tools import gate_programs

    built = _BUILDERS[name](gate_programs)
    prog = built if isinstance(built, Program) \
        else framework.default_main_program()
    assert prog.global_block().ops, name
    assert _errors(verify(prog)) == []
    assert _errors(verify(framework.default_startup_program())) == []
    _roundtrip_stable(prog)


def test_infer_builder_program_roundtrips_through_every_pass():
    """The _build_infer chain (clone-for-test -> InferenceTranspiler ->
    fuse_conv_epilogue -> nhwc -> bf16), pass by pass: green after
    EACH, fingerprint stable after each serialization."""
    from paddle_tpu.contrib.float16 import bf16_transpile
    from paddle_tpu.core.scope import global_scope
    from paddle_tpu.flags import set_flags
    from paddle_tpu.models.resnet import resnet_cifar10
    from paddle_tpu.transpiler import (InferenceTranspiler,
                                       fuse_conv_epilogue,
                                       nhwc_transpile)

    set_flags({"conv_epilogue": "on"})
    try:
        model = resnet_cifar10(depth=8)
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(framework.default_startup_program())
        infer = framework.default_main_program().clone(for_test=True)
        protected = [model["logits"].name]
        fps = [_roundtrip_stable(infer)]
        for passes in (
                lambda p: InferenceTranspiler().transpile(
                    p, protected=protected),
                lambda p: fuse_conv_epilogue(p, protected=protected),
                nhwc_transpile,
                lambda p: bf16_transpile(p, scope=global_scope())):
            passes(infer)
            assert _errors(verify(infer, fetches=protected)) == []
            fps.append(_roundtrip_stable(infer))
        # the passes really rewrote something each time (a fingerprint
        # that never moved would mean the chain tested nothing)
        assert len(set(fps)) == len(fps), fps
    finally:
        set_flags({"conv_epilogue": "off"})


def test_train_program_roundtrips_through_memory_passes():
    from paddle_tpu import layers
    from paddle_tpu.transpiler import memory_optimize, release_memory

    x = layers.data(name="x", shape=[8, 16], dtype="float32",
                    append_batch_size=False)
    h = layers.fc(input=x, size=32, act="relu")
    loss = layers.reduce_mean(layers.fc(input=h, size=4))
    optimizer.Adam(learning_rate=1e-3).minimize(loss)
    prog = framework.default_main_program()
    assert verify(prog, fetches=[loss]) == []
    _roundtrip_stable(prog)
    memory_optimize(prog)
    assert verify(prog, fetches=[loss]) == []
    _roundtrip_stable(prog)
    release_memory(prog)
    assert verify(prog, fetches=[loss]) == []
    _roundtrip_stable(prog)


def test_sharding_annotated_program_verifies_and_roundtrips():
    from paddle_tpu.models.transformer import transformer_encoder_model
    from paddle_tpu.transpiler.sharding_transpiler import \
        ShardingTranspiler

    from paddle_tpu.flags import set_flags

    model = transformer_encoder_model(
        vocab_size=64, max_len=8, d_model=32, n_head=4, d_inner=64,
        n_layer=1, dropout_rate=0.0, param_prefix="tfm")
    optimizer.Adam(learning_rate=1e-3).minimize(model["loss"])
    prog = framework.default_main_program()
    plan = MeshPlan(dp=2, tp=2)
    # transpile() itself runs check_sharding under the suite's
    # ir_verify=on; re-assert explicitly, then the roundtrip property
    set_flags({"gspmd": True})
    try:
        ShardingTranspiler(plan).transpile(prog, min_size=8)
    finally:
        set_flags({"gspmd": False})
    assert check_sharding(prog, plan) == []
    assert _errors(verify(prog)) == []
    fp = _roundtrip_stable(prog)
    # annotations are part of the fingerprint: clearing one changes it
    annotated = [v for v in prog.global_block().vars.values()
                 if v.sharding is not None]
    assert annotated, "tp/zero3 annotated nothing"
    annotated[0].set_sharding(None)
    assert program_fingerprint(prog) != fp


def test_static_shape_check_green_on_built_programs():
    from paddle_tpu import layers

    x = layers.data(name="x", shape=[4, 8], dtype="float32",
                    append_batch_size=False)
    y = layers.fc(input=x, size=16, act="relu")
    loss = layers.reduce_mean(y)
    optimizer.SGD(learning_rate=0.1).minimize(loss)
    assert check_shapes(framework.default_main_program()) == []
