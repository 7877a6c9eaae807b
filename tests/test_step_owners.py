"""Every instruction of the compiled step names the Fluid op that owns
it (ISSUE 51): `core/program.py:op_scope` writes `pt_<role>.<type>`,
`observability/step_owners.py` reads it back from the compiled
module's `op_name` metadata and books a trace's device time by it,
`profiler.device_op_table` and `tools/step_owners.py` print the table.
No test here asserts a duration."""

import contextlib
import importlib.util
import json
import os
import re
import shutil

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import optimizer
from paddle_tpu.observability import step_owners
from paddle_tpu.observability.step_owners import Owner

import test_deepseek_v2_model as dsv2

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEGMENT = ("jit(step)/pt_backward.recompute_segment_grad/"
           "transpose(jvp(pt_backward.recompute_segment_grad))/jvp()/")


@pytest.mark.parametrize("path, want", [
    ("jit(step)/pt_forward.moe_route/pt_moe_route/top_k",
     ("forward", "forward", "moe_route", "pt_moe_route")),
    ("jit(step)/pt_forward.mul/dot_general",
     ("forward", "forward", "mul", None)),
    # a builder's name scope and a kernel's name follow the owner
    ("jit(step)/pt_forward.flash_attention/pt_mla/pt/pt_flash_fwd/"
     "pallas_call",
     ("forward", "forward", "flash_attention", "pt_mla/pt_flash_fwd")),
    ("jit(step)/pt_loss.mean/reduce_sum",
     ("forward", "loss", "mean", None)),
    # a generic grad op: jax.vjp inside the op's compute
    ("jit(step)/pt_backward.mul_grad/transpose(jvp(pt_backward.mul_grad))/"
     "dot_general", ("backward", "backward", "mul_grad", None)),
    ("jit(step)/pt_backward.flash_attention_grad/transpose(jvp(pt))/"
     "pt_flash_bwd_dkv/pallas_call",
     ("backward", "backward", "flash_attention_grad", "pt_flash_bwd_dkv")),
    # inside a recompute segment the owner is the forward op: replayed,
    ("%scheckpoint/rematted_computation/pt_forward.rms_norm/pt_rms_norm/"
     "rsqrt" % SEGMENT, ("replay", "forward", "rms_norm", "pt_rms_norm")),
    # or differentiated
    ("%scheckpoint/pt_forward.moe_experts/pt_moe_experts/pt/"
     "jit(_over_live_rows)/while/body/add" % SEGMENT,
     ("backward", "forward", "moe_experts", "pt_moe_experts")),
    # what the segment's grad op does itself
    (SEGMENT + "remat2",
     ("backward", "backward", "recompute_segment_grad", None)),
    ("jit(step)/pt_optimize.adam/div",
     ("optimize", "optimize", "adam", None)),
    ("jit(step)/pt_lr_sched.increment/add",
     ("other", "lr_sched", "increment", None)),
    ("jit(step)/pt_stat.step_stat/jit(remainder)/rem",
     ("other", "stat", "step_stat", None)),
    # a sub-block's op under the op that runs the block
    ("jit(step)/pt_forward.while/while/body/pt_forward.elementwise_add/add",
     ("forward", "forward", "elementwise_add", None)),
    # no owner: what XLA made itself, a parameter, nothing at all
    ("reduce_sum", (None, None, None, None)),
    ("state['loss_scaling_0']", (None, None, None, None)),
    ("jit(step)/pt_mla/dot_general", (None, None, None, None)),
    ("", (None, None, None, None)),
    (None, (None, None, None, None)),
])
def test_owner_of(path, want):
    assert step_owners.owner_of(path) == Owner(*want)
    if want[0] is not None:
        assert want[0] in step_owners.PASSES


# -- the compiled step of a model --------------------------------------------

def _op_names(text):
    return re.findall(r'op_name="([^"]*)"', text)


def _without_metadata(text):
    """The module's computations with every metadata={...} removed (and
    without the tables of file names and stack frames before them)."""
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    return text[text.index("\n\n%"):]


def _dsv2_step_text(recompute):
    np.random.seed(0)
    model, opt = dsv2._build(dict(dsv2.SMALL), True, recompute,
                             optimizer.Adam(1e-3))
    opt.minimize(model["loss"])
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    compiled = fluid.CompiledProgram(fluid.default_main_program())
    batch = dsv2._batch(dsv2.SMALL)
    feed = {"src_ids": batch[0], "tgt_label": batch[1]}
    exe.run(compiled, feed=feed, fetch_list=[model["loss"]])
    return compiled.step_text(feed)


def test_a_recomputed_step_names_its_owners_and_is_the_same_module(
        monkeypatch, fresh_programs_factory):
    """SMALL dsv2 with Adam, AMP and four recompute segments: nine in
    ten instructions that carry an op_name name an owner, every pass
    occurs; and the module is, but for metadata, the one `op_scope`
    gave when it opened only an op's own name scope."""
    text = _dsv2_step_text(recompute=True)
    found = [step_owners.owner_of(n) for n in _op_names(text)]
    assert len(found) > 5000
    owned = [o for o in found if o.role is not None]
    assert len(owned) >= 0.9 * len(found)
    assert {o.step_pass for o in owned} >= {"forward", "replay",
                                            "backward", "optimize"}
    by_type = {(o.step_pass, o.role, o.type) for o in owned}
    assert ("optimize", "optimize", "adam") in by_type
    # a replayed and a differentiated forward op, and the op's own
    # scope still a path element after its owner
    assert ("replay", "forward", "moe_experts") in by_type
    assert ("backward", "forward", "moe_experts") in by_type
    assert "/pt_forward.moe_route/pt_moe_route/" in text
    per_instruction = step_owners.owners(text)
    assert {"forward", "replay", "backward", "optimize"} <= {
        o.step_pass for o in per_instruction.values()}
    assert not [n for n in per_instruction if n.startswith("%")]

    # as it was: the name scope an op was appended under, or nothing
    def as_it_was(op):
        import jax

        return jax.named_scope(op.scope) if op.scope \
            else contextlib.nullcontext()

    from paddle_tpu.core import compiler, program

    monkeypatch.setattr(program, "op_scope", as_it_was)
    monkeypatch.setattr(compiler, "op_scope", as_it_was)
    with fresh_programs_factory():
        before = _dsv2_step_text(recompute=True)
    assert "pt_forward." not in before and "/pt_moe_route/" in before
    assert _without_metadata(before) == _without_metadata(text)


def test_a_generic_grad_op_is_found_by_type():
    """Without segments every forward op has its grad op in the block:
    `mul_grad` (registry: jax.vjp of the forward compute) is a backward
    owner, and nothing is a replay."""
    found = step_owners.owners(_dsv2_step_text(recompute=False))
    by_type = {(o.step_pass, o.role, o.type) for o in found.values()}
    assert ("backward", "backward", "mul_grad") in by_type
    assert ("optimize", "optimize", "adam") in by_type
    assert "replay" not in {o.step_pass for o in found.values()}


def test_what_jax_lowers_once_a_module():
    """Two limits of reading owners from `op_name`, as jax 0.9 has
    them.  An inner jax.jit is lowered ONCE a module, one function with
    a call from each owner, and the compiler's inliner gives each copy
    its own caller's path: nothing is lost there (ISSUE 51 feared the
    first caller's).  A primitive whose lowering jax caches a module
    (cumsum's reduce-window, a sort's comparator) carries its bare name
    and no path: `cumsum` under an owner leaves instructions that no
    owner can be read from."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def helper(x, w):
        return jnp.sin(x @ w) * 2

    def step(x, w):
        with jax.named_scope("pt_forward.first"):
            a = helper(x, w)
        with jax.named_scope("pt_forward.second"):
            b = helper(a, w)
        with jax.named_scope("pt_forward.third"):
            return jnp.cumsum(b, axis=0)

    x = jnp.ones((128, 128), jnp.float32)
    lowered = jax.jit(step).lower(x, x)
    module = lowered.as_text()
    assert len(re.findall(r"func\.func \w+ @helper", module)) == 1
    assert module.count("call @helper(") == 2
    names = _op_names(lowered.compile().as_text())
    for owner in ("first", "second"):
        for primitive in ("dot_general", "sin"):
            assert "jit(step)/pt_forward.%s/jit(helper)/%s" % (
                owner, primitive) in names
    bare = [n for n in names if n == "reduce_window_sum"]
    assert bare and step_owners.owner_of(bare[0]).role is None


HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %mul.9 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(step)/pt_optimize.adam/mul" stack_frame_id=3}
}

%fused_computation.2 (p0.1: f32[8]) -> f32[8] {
  %p0.1 = f32[8]{0} parameter(0)
  %neg.1 = f32[8]{0} negate(%p0.1), metadata={op_name="jit(step)/pt_forward.scale/neg"}
  ROOT %add.3 = f32[8]{0} add(%neg.1, %p0.1), metadata={op_name="jit(step)/pt_forward.relu/pt_act/max"}
}

%body.4 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%arg), index=1
  %fusion.7 = f32[8]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.2
  %copy.8 = f32[8]{0} copy(%fusion.7)
  %gather.6 = f32[8]{0} gather(%copy.8, %gte.1), metadata={op_name="while/body/jit(_take)/gather"}
  ROOT %tuple.2 = (s32[], f32[8]{0}) tuple(%gte.1, %gather.6)
}

%cond.5 (arg.1: (s32[], f32[8])) -> pred[] {
  %arg.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt.1 = pred[] constant(true)
}

ENTRY %main.9 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/pt_backward.mul_grad/transpose(jvp(pt_backward.mul_grad))/mul"}
  %while.3 = (s32[], f32[8]{0}) while(%tuple.0), condition=%cond.5, body=%body.4, metadata={op_name="jit(step)/pt_forward.while/while"}
  %copy.2 = f32[8]{0} copy(%x)
  ROOT %sort.11 = f32[8]{0} sort(%copy.2), dimensions={0}, metadata={op_name="sort"}
}
"""


def test_owners_of_a_modules_text():
    """Entry, loop body and condition are read, fused computations are
    not; a fusion is owned by its own metadata and, without any, by its
    computation's root; what names no owner inside a loop (the
    compiler's copy, a path that starts at the loop) by the `while`
    that runs it, and outside one by nobody."""
    found = step_owners.owners(HLO)
    assert "mul.9" not in found and "neg.1" not in found
    assert found["fusion.1"] == Owner("backward", "backward", "mul_grad",
                                      None)
    assert found["fusion.7"] == Owner("forward", "forward", "relu",
                                      "pt_act")
    assert found["while.3"] == Owner("forward", "forward", "while", None)
    assert found["copy.8"] == found["gather.6"] == found["lt.1"] \
        == found["while.3"]
    none = Owner(None, None, None, None)
    assert found["copy.2"] == found["sort.11"] == found["x"] == none
    assert step_owners.owners("") == {} == step_owners.owners(None)


def test_device_time_counts_a_loop_once_and_keeps_what_has_no_owner():
    found = step_owners.owners(HLO)
    events = [
        # named by the instruction's whole text, as the profiler does
        ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop, "
         "calls=%fused_computation.1", 0, 10),
        # a loop's event spans its body's, on the same line
        ("%while.3 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) "
         "%tuple.0), condition=%cond.5, body=%body.4", 10, 70),
        ("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %gte.1), kind=kLoop, "
         "calls=%fused_computation.2", 12, 32),
        ("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %gte.1), kind=kLoop, "
         "calls=%fused_computation.2", 40, 65),
        ("%copy.8 = f32[8]{0} copy(f32[8]{0} %fusion.7)", 65, 69),
        ("%copy.2 = f32[8]{0} copy(f32[8]{0} %x)", 70, 73),
        ("%sort.11 = f32[8]{0} sort(f32[8]{0} %copy.2), dimensions={0}",
         73, 80),
        # an instruction of another module, or by its name alone
        ("custom-thing.5", 80, 84),
    ]
    rows = step_owners.device_time(events, found)
    assert rows == [
        ("forward", "forward.relu", "pt_act", "fusion", 2, 45),
        ("backward", "backward.mul_grad", "-", "fusion", 1, 10),
        ("other", "-", "-", "sort", 1, 7),
        ("forward", "forward.while", "-", "copy", 1, 4),
        ("other", "-", "-", "custom-thing", 1, 4),
        ("other", "-", "-", "copy", 1, 3),
    ]
    leaves = [e for e in events if " while(" not in e[0]]
    assert sum(r[5] for r in rows) == sum(e - s for _, s, e in leaves) == 73
    assert step_owners.device_time([], found) == []
    table = step_owners.format_table(rows, steps=2)
    lines = table.splitlines()
    assert lines[0].split() == ["Event", "Calls", "Total(ms)", "Ave(ms)",
                                "Share"]
    # a pass, then the owners within it; the unowned by their names, last
    heads = [ln.split()[1] for ln in lines if ln.startswith("== ")]
    assert heads == ["forward", "backward", "other"]
    assert "forward.relu [pt_act]" in lines[2]
    assert lines[3].split()[0] == "forward.while"
    assert [ln.split()[0] for ln in lines[-3:]] == ["sort", "custom-thing",
                                                   "copy"]
    shares = [float(ln.split()[-1].rstrip("%")) for ln in lines
              if ln.startswith("== ")]
    assert sum(shares) == pytest.approx(100.0, abs=0.2)
    # sorted by calls: the two fusions of the loop come first
    by_calls = step_owners.format_table(rows, sorted_key="calls")
    assert [ln.split()[1] for ln in by_calls.splitlines()
            if ln.startswith("== ")] == heads
    assert set(by_calls.splitlines()) == set(
        step_owners.format_table(rows).splitlines())


# -- the table from a trace ---------------------------------------------------

TRACE = os.path.join(CHECKOUT, "benchmarks", "tests", "data",
                     "record_trace_1chip.xplane.pb")


class _Step:
    """What device_op_table asks of a CompiledProgram."""

    def __init__(self, text):
        self.text, self.feeds = text, []

    def step_text(self, feed):
        self.feeds.append(feed)
        return self.text


def test_device_op_table_reads_a_chips_trace(tmp_path, capsys):
    """benchmarks/tests/data/record_trace_1chip.xplane.pb: four
    executions of `jit_body` (a flash kernel, a convolution, a matmul,
    copies) with two other modules between them.  Joined to a text that
    owns two of its instructions, the table holds the step module's
    events only and every nanosecond of them."""
    from jax.profiler import ProfileData

    logdir = tmp_path / "plugins" / "profile" / "t"
    logdir.mkdir(parents=True)
    shutil.copy(TRACE, logdir / "host.xplane.pb")
    text = """HloModule jit_body

ENTRY %main (q: f32[8]) -> f32[8] {
  %body.1 = f32[8]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(body)/pt_forward.flash_attention/pt/pt_flash_fwd/pallas_call"}
  ROOT %add_add_fusion = f32[8]{0} fusion(%q), kind=kOutput, calls=%fc, metadata={op_name="jit(body)/pt_backward.mul_grad/transpose(jvp(pt_backward.mul_grad))/dot_general"}
}
"""
    step = _Step(text)
    rows, steps = fluid.profiler.device_op_table(str(tmp_path), step,
                                                 {"q": 1})
    assert steps == 4 and step.feeds == [{"q": 1}]
    plane = next(p for p in ProfileData.from_file(TRACE).planes
                 if p.name == "/device:TPU:0")
    lines = {ln.name: list(ln.events) for ln in plane.lines}
    runs = [(e.start_ns, e.start_ns + e.duration_ns)
            for e in lines["XLA Modules"] if e.name.startswith("jit_body(")]
    inside = [e for e in lines["XLA Ops"]
              if any(s <= e.start_ns < t for s, t in runs)]
    assert 0 < len(inside) < len(lines["XLA Ops"])
    assert sum(r[4] for r in rows) == len(inside)
    assert sum(r[5] for r in rows) == sum(e.duration_ns for e in inside)
    owned = {r[:4]: r[4] for r in rows if r[1] != "-"}
    assert owned == {
        ("forward", "forward.flash_attention", "pt_flash_fwd", "body"): 4,
        ("backward", "backward.mul_grad", "-", "add_add_fusion"): 4}
    assert {r[3] for r in rows if r[1] == "-"} >= {"copy", "copy-done"}
    table = capsys.readouterr().out
    assert table.splitlines()[0].split()[0] == "Event"
    assert "forward.flash_attention [pt_flash_fwd]" in table
    # two traces under one directory: which one is not for the table to
    # guess
    shutil.copy(TRACE, logdir / "second.xplane.pb")
    with pytest.raises(RuntimeError, match="expected one"):
        fluid.profiler.device_op_table(str(tmp_path), step, {})


# -- tools/step_owners.py on no chip -----------------------------------------

TINY_TFM = {
    "builder": "transformer_lm", "reference": "transformer_lm",
    "n_layer": 2, "d_model": 64, "d_inner": 128, "n_head": 2,
    "vocab_size": 128, "dropout_rate": 0.0, "label_smooth_eps": 0.0,
    "amp": True, "learning_rate": 1e-3, "param_prefix": "tfm",
    "kernel_impls": {"flash_attention": "xla"}, "reference_rtol": 2e-2,
}


@pytest.fixture
def tool_and_root(tmp_path):
    """A checkout of one tiny cell: the benchmark's own harness, loop
    kind and builder beside a configuration and a job of the test's."""
    bench = tmp_path / "benchmarks"
    bench.mkdir()
    src = os.path.join(CHECKOUT, "benchmarks")
    for name in ("harness.py", "flops.py", "builders", "kinds"):
        path = os.path.join(src, name)
        (shutil.copytree if os.path.isdir(path) else shutil.copy)(
            path, bench / name)
    (bench / "configs").mkdir()
    (bench / "traffic").mkdir()
    (bench / "configs" / "tiny-tfm.json").write_text(json.dumps(TINY_TFM))
    (bench / "traffic" / "tiny_seq.json").write_text(json.dumps(
        {"kind": "train_steps", "batch": 4, "seq_len": 16,
         "rate_metric": "tokens_per_s"}))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "paths": ["benchmarks"],
        "configs": [{"name": "tiny-tfm",
                     "file": "benchmarks/configs/tiny-tfm.json"}],
        "workloads": [{"name": "c_seq", "config": "tiny-tfm",
                       "traffic": "tiny_seq", "chips": 1}]}))
    spec = importlib.util.spec_from_file_location(
        "step_owners_tool", os.path.join(CHECKOUT, "tools",
                                         "step_owners.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool, str(tmp_path)


def test_the_tool_builds_a_cell_traces_it_and_prints_the_table(
        tool_and_root, monkeypatch, capsys, tmp_path):
    """Everything but the device's own plane, which a CPU trace lacks:
    the cell through the benchmark's builder, warm and traced steps, ONE
    .xplane.pb handed to device_op_table with the step and a feed whose
    text names owners; the table's rows (here: made up) summed by pass
    and share owned."""
    tool, root = tool_and_root
    seen = {}

    def table(logdir, compiled, feed, sorted_key="total"):
        traces = [f for _, _, files in os.walk(logdir) for f in files
                  if f.endswith(".xplane.pb")]
        found = step_owners.owners(compiled.step_text(feed))
        seen.update(traces=traces, sorted_key=sorted_key,
                    passes={o.step_pass for o in found.values()})
        return [("forward", "forward.mul", "-", "fusion", 6, 6_000_000),
                ("optimize", "optimize.adam", "-", "fusion", 30, 3_000_000),
                ("other", "-", "-", "copy", 3, 1_000_000)], 3

    monkeypatch.setattr(fluid.profiler, "device_op_table", table)
    out = str(tmp_path / "o" / "rows.json")
    assert tool.main(["--root", root, "--workload", "c_seq", "--steps", "3",
                      "--platform", "cpu", "--sorted-key", "calls",
                      "--out", out]) == 0
    assert len(seen["traces"]) == 1
    assert seen["sorted_key"] == "calls"
    assert seen["passes"] >= {"forward", "backward", "optimize"}
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["workload"] == "c_seq" and line["steps"] == 3
    assert line["device_ms_per_step"] == pytest.approx(10 / 3)
    assert line["owned_pct"] == pytest.approx(90.0)
    assert line["pass_ms_per_step"] == pytest.approx(
        {"forward": 2.0, "optimize": 1.0, "other": 1 / 3})
    assert np.isfinite(line["last_loss"])
    kept = json.load(open(out))
    assert len(kept["rows"]) == 3 and kept["owned_pct"] == line["owned_pct"]
    # the trace's directory does not outlive the run
    assert not os.path.exists(os.path.join(
        root, "benchmarks", "out", "_owners_c_seq"))
    # another platform than the one asked for: no table, exit 2
    assert tool.main(["--root", root, "--workload", "c_seq"]) == 2
